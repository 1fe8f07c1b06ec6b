"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a cell at a CPU size (device check
skipped) with one fault planted in the program: a step that returns its
state unchanged, half of the batch left out of the statistics or the
search, or an answer altered where it is produced. The exchange between
chips has no fault to plant: every cell runs on one chip."""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest
from conftest import CPU_DEVICE

from bench import run
from repro.core import kmeans as kmeans_mod
from repro.index import IVFIndex
from repro.kernels import ops


def _run(cell):
    return run.execute(cell, 2147483701, 0.3, False,
                       t0=time.perf_counter(), device=dict(CPU_DEVICE))


def _fit_unchanged(mp):
    orig = kmeans_mod.lloyd_step

    def step(x, c, cfg, blk=None):
        _c_new, a, j = orig(x, c, cfg, blk)
        return c, a, j
    mp.setattr(kmeans_mod, "lloyd_step", step)


def _fit_half(mp):
    orig = kmeans_mod.lloyd_stats

    def stats(x, c, cfg, blk=None):
        a, _s, _cnt, j = orig(x, c, cfg, blk)
        h, k = x.shape[0] // 2, c.shape[0]
        s = jnp.zeros((k, x.shape[1]), jnp.float32).at[a[:h]].add(x[:h])
        cnt = jnp.zeros((k,), jnp.float32).at[a[:h]].add(1.0)
        return a, s, cnt, j
    mp.setattr(kmeans_mod, "lloyd_stats", stats)


def _fit_altered(mp):
    orig = ops.finalize_centroids

    def finalize(s, cnt, c):   # moves the centroid of the largest cluster
        return orig(s, cnt, c).at[jnp.argmax(cnt), 0].add(1.0)
    mp.setattr(ops, "finalize_centroids", finalize)


def _search_unchanged(mp):
    orig, first = IVFIndex.search, {}

    def search(self, q, *a, **kw):
        if "out" not in first:
            first["out"] = orig(self, q, *a, **kw)
        return first["out"]
    mp.setattr(IVFIndex, "search", search)


def _search_half(mp):
    orig = IVFIndex.search

    def search(self, q, *a, **kw):
        ids, d = orig(self, q, *a, **kw)
        h = q.shape[0] // 2
        return (jnp.concatenate([ids[:h], ids[:q.shape[0] - h]]),
                jnp.concatenate([d[:h], d[:q.shape[0] - h]]))
    mp.setattr(IVFIndex, "search", search)


def _search_altered(mp):
    orig = IVFIndex.search

    def search(self, q, *a, **kw):
        ids, d = orig(self, q, *a, **kw)
        return ids + 1, d
    mp.setattr(IVFIndex, "search", search)


@pytest.mark.parametrize("fault", [_fit_unchanged, _fit_half, _fit_altered])
def test_fit_fault_is_not_correct(tiny_cell, monkeypatch, fault):
    cell = tiny_cell("fit_k1024_d128")
    fault(monkeypatch)
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("name,fault", [
    ("search_backlog", _search_unchanged),
    ("search_backlog", _search_half),
    ("search_open_loop", _search_altered)])
def test_search_fault_is_not_correct(tiny_cell, monkeypatch, name, fault):
    cell = tiny_cell(name)
    fault(monkeypatch)
    assert not _run(cell)["correct"]


def test_sound_runs_are_correct(tiny_cell):
    for name in ("fit_k1024_d128", "search_backlog"):
        assert _run(tiny_cell(name))["correct"]
