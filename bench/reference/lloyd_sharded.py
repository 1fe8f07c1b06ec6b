"""Plain Lloyd step over points sharded along N across devices.

``bench/reference/lloyd.py`` applied to each device's rows on that
device (one ``shard_map`` over the points' own mesh), the per-device
results combined exactly: gaps by their maximum, cluster sums, counts,
inertias and mismatch counts added in float64 on the host. No device
ever holds more of the points than its own shard.

``numbers`` gives the same ``assign_gap``, ``update_gap``,
``inertia_gap`` and ``assign_mismatch`` that ``bench.compare.step_numbers``
defines for a step over the whole array, up to the order of float32
sums: the means the update is held to are formed from float64 sums of
per-device float32 sums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bench import compare
from bench.reference import lloyd as ref_lloyd


def _axes(x):
    """The mesh and the mesh axes that shard ``x``'s rows."""
    return x.sharding.mesh, x.sharding.spec[0]


def _spmd(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.partial(jax.jit, static_argnames=("mesh", "axes", "precision"))
def _assign(x, c, *, mesh, axes, precision):
    return _spmd(lambda xs, c: ref_lloyd.assign(xs, c, precision), mesh,
                 (P(axes, None), P()), (P(axes), P(axes)))(x, c)


def assign(x, c, precision: str = "highest"):
    """Nearest centroid of every point and its squared distance, both
    sharded like ``x``'s rows."""
    mesh, axes = _axes(x)
    return _assign(x, c, mesh=mesh, axes=axes, precision=precision)


@functools.partial(jax.jit, static_argnames=("mesh", "axes", "k", "half"))
def _stats(x, a, *, mesh, axes, k, half):
    def body(xs, a):
        if half:
            xs, a = xs[:xs.shape[0] // 2], a[:a.shape[0] // 2]
        s = jnp.zeros((k, xs.shape[1]), jnp.float32).at[a].add(xs)
        cnt = jnp.zeros((k,), jnp.float32).at[a].add(1.0)
        return s[None], cnt[None]
    return _spmd(body, mesh, (P(axes, None), P(axes)),
                 (P(axes), P(axes)))(x, a)


def cluster_means(x, a, k: int, half: bool = False):
    """Float64 means of the points each cluster holds (zero where it holds
    none) and the counts; ``half`` takes only the first half of each
    device's rows (a planted fault)."""
    mesh, axes = _axes(x)
    s, cnt = _stats(x, a, mesh=mesh, axes=axes, k=k, half=half)
    s = np.asarray(s, np.float64).sum(axis=0)
    cnt = np.asarray(cnt, np.float64).sum(axis=0)
    return s / np.maximum(cnt, 1.0)[:, None], cnt


def step(x, c, precision: str = "highest"):
    """``(centroids, assignments, inertia)`` of one Lloyd step from ``c``:
    the centroids a host array, the assignments sharded like ``x``."""
    a, m = assign(x, c, precision)
    mean, cnt = cluster_means(x, a, c.shape[0])
    c_new = np.where((cnt > 0)[:, None], mean, np.asarray(c, np.float64))
    return c_new.astype(np.float32), a, _sum(m)


@functools.partial(jax.jit, static_argnames=("mesh", "axes"))
def _sums(m, *, mesh, axes):
    return _spmd(lambda m: jnp.sum(m)[None], mesh, P(axes), P(axes))(m)


def _sum(m) -> float:
    """The sum of a row-sharded float32 vector: per-device float32 sums
    added in float64."""
    mesh, axes = _axes(m)
    return float(np.asarray(_sums(m, mesh=mesh, axes=axes),
                            np.float64).sum())


@functools.partial(jax.jit, static_argnames=("mesh", "axes"))
def _shard_numbers(x, c0, a, a_ref, *, mesh, axes):
    def body(xs, c0, a, a_ref):
        gap = compare._assign_gap(xs, c0, a, a_ref)
        return gap[None], jnp.sum(a != a_ref)[None]
    return _spmd(body, mesh, (P(axes, None), P(), P(axes), P(axes)),
                 (P(axes), P(axes)))(x, c0, a, a_ref)


def numbers(x, c0, step, ref) -> dict[str, float]:
    """``bench.compare.step_numbers`` of a step over row-sharded ``x``:
    ``step`` the program's ``(centroids, assignments, inertia)`` from
    ``c0``, the assignments sharded like ``x``; ``ref`` the reference's
    ``(assignments, distances)`` from ``assign``."""
    c, a, j = step
    a_r, m_r = ref
    mesh, axes = _axes(x)
    a = jax.device_put(a, a_r.sharding)
    gaps, miss = _shard_numbers(x, c0, a, a_r, mesh=mesh, axes=axes)
    mean, cnt = cluster_means(x, a, c0.shape[0])
    live = (cnt > 0)[:, None]
    c = np.asarray(c, np.float64)
    update_gap = (np.max(np.where(live, np.abs(c - mean), 0.0))
                  / np.max(np.where(live, np.abs(mean), 0.0)))
    j, j_r = float(j), _sum(m_r)
    return {
        "assign_gap": float(np.max(np.asarray(gaps))),
        "update_gap": float(update_gap),
        "inertia_gap": abs(j - j_r) / j_r if np.isfinite(j) else float("inf"),
        "assign_mismatch": float(np.asarray(miss, np.float64).sum()
                                 / x.shape[0]),
    }
