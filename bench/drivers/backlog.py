"""Traffic: offline batch search with a standing backlog.

Traffic parameters: ``request_rows`` (queries per request), ``ahead``
(requests kept queued ahead of the engine) and ``pool_requests``
(distinct requests, cycled in a seed-drawn order). The window keeps
``ahead`` requests in the engine's queue, pumps one unit at a time, and
takes each request's ids to the host once the pipeline holds as many
units as its depth; at ``--seconds`` it stops submitting, drains, and
closes at the last completion. ``search_qps`` is the queries completed
over the window.

The check draws ``check_requests`` completed requests from the seed and
compares their ids and distances with brute force over the corpus.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench.drivers import _search


def setup(cell: dict, seed: int, spans) -> dict:
    p = cell["workload"]["params"]
    rows = p["request_rows"]
    st = _search.setup(cell, seed, p["pool_requests"] * rows)
    st["p"] = p
    st["pool"] = st["q"].reshape(p["pool_requests"], rows, -1)
    st["order"] = np.random.default_rng(seed).permutation(p["pool_requests"])
    t = time.perf_counter()
    eng = st["engine"]
    eng.take(eng.submit(st["pool"][0]))[0].block_until_ready()
    st["setup_parts"]["warm-up"] = time.perf_counter() - t
    return st


def window(st: dict, seconds: float, spans) -> dict:
    eng, pool, order, p = st["engine"], st["pool"], st["order"], st["p"]
    depth = max(1, eng.scfg.pipeline_depth)
    queued = collections.deque()       # (pool index, request id)
    done = []                          # (pool index, ids, dists)
    units0, served0 = eng.batches_formed, eng.queries_served
    n_sub = 0

    def take_oldest():
        with spans("take"):
            i, rid = queued.popleft()
            ids, dists = eng.take(rid)
            done.append((i, np.asarray(ids), np.asarray(dists)))

    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with spans("submit"):
            while eng.queue_depth < p["ahead"]:
                i = int(order[n_sub % len(order)])
                queued.append((i, eng.submit(pool[i])))
                n_sub += 1
        with spans("pump"):
            eng.pump(1)
        while len(queued) - eng.queue_depth >= depth:
            take_oldest()
    with spans("pump"):
        eng.pump()
    while queued:
        take_oldest()
    window_s = time.perf_counter() - t0
    st["done"] = done
    n_q = sum(ids.shape[0] for _i, ids, _d in done)
    return {"attempted": n_sub, "failed": n_sub - len(done),
            "window_s": window_s, "e2e": {"search_qps": n_q / window_s},
            "units": eng.batches_formed - units0,
            "queries": eng.queries_served - served0}


def layer_record(st: dict, rec: dict) -> dict:
    from bench import cells
    import jax
    peaks_of = cells.peaks(jax.devices()[0].device_kind)
    used = sorted({i for i, _ids, _d in st["done"]})
    least = dict(zip(used, _search.unit_least_times(
        st, [st["pool"][i] for i in used], peaks_of)))
    return {"least_time_s": sum(least[i] for i, _ids, _d in st["done"])}


def release(st: dict) -> None:
    _search.release(st)


def check(st: dict, seed: int) -> dict[str, float]:
    done = st["done"]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(done), min(st["p"]["check_requests"], len(done)),
                      replace=False)
    rows = np.concatenate([st["pool"][done[j][0]] for j in pick])
    ids = np.concatenate([done[j][1] for j in pick])
    dists = np.concatenate([done[j][2] for j in pick])
    return _search.check(st, rows, ids, dists)


def control(cell: dict, seed: int, precision: str) -> dict[str, float]:
    p = cell["workload"]["params"]
    n = p["check_requests"] * p["request_rows"]
    return _search.control(cell, precision,
                           _search.held_out_rows(cell, seed, n))
