"""Traffic: online search, open loop, one query per request.

No cell of ``BENCHMARK.json`` uses it yet: at 320 requests/s, 0.8 x the
knee its sweep found, its p99 split into two regimes from run to run
(``PERF.md``, Open questions). ``bench/workloads/search_open_loop.json``
keeps that traffic for the cell a later benchmark PR adds.

Traffic parameters: ``rate`` (requests per second, fixed in the cell),
``pool_queries`` (distinct queries, at least ``rate * --seconds``) and
``check_requests``. Arrivals are a Poisson process: every seed gets the
same set of inter-arrival gaps (drawn from a fixed key) in its own order,
and its own queries. One thread plays both sides: it submits every
request that is due, pumps the engine, and takes the ids of the oldest
group of requests to the host, in one transfer, once a newer group is
in flight (or when nothing is due). Each request is timed from its due
time to its ids being on the host; requests due in ``--seconds`` are all served, and
the window closes at the last completion. ``search_p99_ms`` is the 99th
percentile over all of them; how late the generator submitted is kept
as ``lag_ms``.

Set-up warms every unit size the traffic can form (1 to ``query_batch``
requests), since the engine's per-request slicing, concatenation and
padding run as small programs for each size.

The check draws ``check_requests`` served requests from the seed and
compares them with brute force over the corpus.
"""
from __future__ import annotations

import collections
import time

import jax
import numpy as np

from bench.drivers import _search

_GAP_KEY = 20260415   # one fixed set of gaps for every seed


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s) of the requests in the window."""
    n = int(rate * seconds * 1.5) + 64
    gaps = np.random.default_rng(_GAP_KEY).exponential(1.0 / rate, n)
    due = np.cumsum(np.random.default_rng(seed).permutation(gaps))
    return due[due < seconds]


def setup(cell: dict, seed: int, spans) -> dict:
    p = cell["workload"]["params"]
    st = _search.setup(cell, seed, p["pool_queries"])
    st["p"], st["seed"] = p, seed
    eng = st["engine"]
    t = time.perf_counter()
    for n in range(1, st["cfg"]["query_batch"] + 1):
        rids = [eng.submit(st["q"][i:i + 1]) for i in range(n)]
        eng.pump()
        jax.block_until_ready([eng.take(r) for r in rids])
    st["setup_parts"]["warm-up"] = time.perf_counter() - t
    return st


def window(st: dict, seconds: float, spans) -> dict:
    eng, q, p = st["engine"], st["q"], st["p"]
    due = arrivals(p["rate"], seconds, st["seed"])
    n = len(due)
    if n > len(q):
        raise ValueError(f"{n} requests due but only {len(q)} queries")
    depth = max(1, eng.scfg.pipeline_depth)
    rids = np.zeros(n, np.int64)
    lat, lag = np.zeros(n), np.zeros(n)
    results = {}
    groups = collections.deque()
    units0, served0 = eng.batches_formed, eng.queries_served
    sub = taken = 0
    t0 = time.perf_counter()
    while taken < n:
        now = time.perf_counter() - t0
        if sub < n and due[sub] <= now:
            first = sub
            with spans("submit"):
                while sub < n and due[sub] <= now:
                    rids[sub] = eng.submit(q[sub:sub + 1])
                    lag[sub] = time.perf_counter() - t0 - due[sub]
                    sub += 1
            with spans("pump"):
                eng.pump()
            groups.append(range(first, sub))
            if len(groups) < depth:
                continue
        if groups:
            with spans("take"):
                group = groups.popleft()
                got = [eng.take(int(rids[i])) for i in group]
                ids = jax.device_get([g[0] for g in got])
                done_at = time.perf_counter() - t0
            for i, g, host_ids in zip(group, got, ids):
                results[i] = (host_ids, g[1])
                lat[i] = done_at - due[i]
            taken += len(group)
        elif sub < n:
            time.sleep(max(0.0, due[sub] - (time.perf_counter() - t0)))
    window_s = time.perf_counter() - t0
    st["results"] = results
    lat_ms = lat * 1e3
    return {"attempted": n, "failed": n - len(results), "window_s": window_s,
            "e2e": {"search_p99_ms": float(np.percentile(lat_ms, 99))},
            "latency_ms": lat_ms, "lag_ms": lag * 1e3,
            "units": eng.batches_formed - units0,
            "queries": eng.queries_served - served0}


def layer_record(st: dict, rec: dict) -> dict:
    return {}


def release(st: dict) -> None:
    _search.release(st)


def check(st: dict, seed: int) -> dict[str, float]:
    res = st["results"]
    keys = sorted(res)
    pick = np.random.default_rng(seed).choice(
        len(keys), min(st["p"]["check_requests"], len(keys)), replace=False)
    idx = [keys[j] for j in pick]
    return _search.check(st, st["q"][idx],
                         np.concatenate([res[i][0] for i in idx]),
                         np.concatenate(jax.device_get([res[i][1]
                                                        for i in idx])))


def control(cell: dict, seed: int, precision: str) -> dict[str, float]:
    return _search.control(cell, precision, _search.held_out_rows(
        cell, seed, cell["workload"]["params"]["check_requests"]))
