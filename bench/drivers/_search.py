"""Set-up and check shared by the search drivers.

The corpus is ``n`` float32 rows of width ``d`` around ``n_centers``
Gaussian centres, made on the device from the configuration's
``corpus_seed``: it stands for the deployment's one data set, and it
fixes the index's largest list, which sets the gather width and so the
work of every unit (two corpora drawn apart can differ twofold in it).
The run's seed draws the queries: fresh draws from the corpus's
distribution (never corpus rows), made on the device and held on the
host as requests arrive there. The index is ``IVFIndex.build`` at the
configuration's settings, served by a ``SearchEngine``.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from bench import compare, data
from bench.counts import ivf_search as counts
from bench.reference import topk as ref_topk


def setup(cell: dict, seed: int, n_queries: int) -> dict:
    from repro.index import IVFIndex
    from repro.serve.engine import SearchConfig, SearchEngine
    cfg = cell["config"]
    t = time.perf_counter()
    x = corpus(cfg)
    q = held_out_rows(cell, seed, n_queries)
    t_data = time.perf_counter() - t
    t = time.perf_counter()
    index = IVFIndex.build(x, k=cfg["k"], max_iters=cfg["build_iters"],
                           init=cfg["build_init"], seed=cfg["corpus_seed"],
                           store=cfg["store"], codec=cfg["codec"],
                           router=cfg["router"])
    index.block_until_ready()
    t_build = time.perf_counter() - t
    engine = SearchEngine(index, SearchConfig(
        topk=cfg["topk"], nprobe=cfg["nprobe"],
        query_batch=cfg["query_batch"]), health=None)
    return {"cfg": cfg, "x": x, "q": q, "index": index, "engine": engine,
            "setup_parts": {"data": t_data, "index build": t_build}}


def _corpus_key(cfg: dict):
    return data.base_key(cfg["corpus_seed"])


def corpus(cfg: dict):
    return data.blobs(_corpus_key(cfg), cfg["n"], cfg["d"], cfg["n_centers"])


def held_out_rows(cell: dict, seed: int, n: int) -> np.ndarray:
    """``n`` queries drawn from the seed."""
    cfg = cell["config"]
    return np.asarray(data.held_out(data.base_key(seed), n, cfg["d"],
                                    cfg["n_centers"], _corpus_key(cfg)))


def unit_least_times(st: dict, batches: list[np.ndarray], peaks_of) -> list:
    """The least time of each unit of queries on the chip, from the lists
    its queries probe (the exact top-nprobe over the index's centroids)
    and the real rows of each list."""
    cfg, index = st["cfg"], st["index"]
    cents = jnp.asarray(index.centroids, jnp.float32)
    n_rows = np.asarray(index.counts)
    out = []
    for qb in batches:
        probed, _ = ref_topk.topk(jnp.asarray(qb), cents, k=cfg["nprobe"])
        out.append((np.asarray(probed), n_rows))
    return [counts.least_time_s(pr, nr, cfg["k"], cfg["d"], peaks_of)
            for pr, nr in out]


def release(st: dict) -> None:
    del st["engine"], st["index"]


def check(st: dict, rows: np.ndarray, ids: np.ndarray, dists: np.ndarray
          ) -> dict[str, float]:
    """The numbers of served queries ``rows`` against brute force."""
    return compare.search_numbers(st["x"], jnp.asarray(rows), ids, dists,
                                  st["cfg"]["topk"])


def control(cell: dict, precision: str, rows: np.ndarray
            ) -> dict[str, float]:
    """Brute force at ``precision`` in the index's place, judged by the
    same numbers: the control of a limit."""
    cfg = cell["config"]
    x = corpus(cfg)
    q = jnp.asarray(rows)
    ids, score = ref_topk.topk(q, x, k=cfg["topk"], precision=precision)
    dists = score + jnp.sum(q * q, axis=-1, keepdims=True)
    return compare.search_numbers(x, q, ids, dists, cfg["topk"])

