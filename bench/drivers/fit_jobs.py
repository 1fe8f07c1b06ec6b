"""Traffic: back-to-back k-means fit jobs, one Lloyd step at a time.

Traffic parameters: ``n``, ``k``, ``d`` (points, clusters, width) and
``iters`` (Lloyd steps per job). The points are ``n`` float32 rows
around ``k`` Gaussian centres, made once on the device from the seed;
each job fits them again from its own initial centroids, ``k`` distinct
points (a restart, as a user runs several fits and keeps the best), and
runs ``iters`` steps through ``KMeans.iterate``, the program's public
Lloyd step, each dispatched on the last one's centroids with no host
sync inside the job; a job ends when its last inertia is on the host.
Set-up runs one job. The window starts jobs one after another until
``--seconds`` have passed and closes when the last one finishes;
``lloyd_iter_ms`` is the window over the steps completed.

The window drives the step and not ``KMeans.fit``, whose while loop
returns only its last step: after a few steps two sound fits part on
near-ties that compound, so a fit's answer cannot tell the program from
a fit at lower precision. Each job's first step starts from centroids the
benchmark draws from the seed, so the reference recomputes that step
exactly; the check draws one job of the window from the seed and
compares its first step (``bench.compare.step_numbers``).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, data
from bench.reference import lloyd as ref_lloyd


@functools.partial(jax.jit, static_argnames="k")
def _init(x, perm, job, *, k: int):
    """Job ``job``'s initial centroids: ``k`` distinct points, the job's
    own stretch of a seeded permutation of the rows."""
    n = x.shape[0]
    return jnp.take(x, jnp.take(perm, (job * k + jnp.arange(k)) % n), axis=0)


def _data(p: dict, seed: int):
    key = data.base_key(seed)
    x = data.blobs(jax.random.fold_in(key, 1), p["n"], p["d"], p["k"])
    return x, jax.random.permutation(jax.random.fold_in(key, 3), p["n"])


def setup(cell: dict, seed: int, spans) -> dict:
    from repro.core import KMeans, KMeansConfig
    p, cfg = cell["workload"]["params"], cell["config"]
    t = time.perf_counter()
    x, perm = _data(p, seed)
    perm.block_until_ready()
    t_data = time.perf_counter() - t
    km = KMeans(KMeansConfig(k=p["k"], max_iters=p["iters"], tol=cfg["tol"],
                             init=cfg["init"]))
    st = {"x": x, "perm": perm, "km": km, "p": p}
    t = time.perf_counter()
    _job(st, 0)
    st["setup_parts"] = {"data": t_data,
                         "warm-up job": time.perf_counter() - t}
    return st


def _job(st: dict, i: int):
    """Run job ``i``; returns its first step ``(centroids, assignments,
    inertia)`` and the number of steps."""
    km, x, p = st["km"], st["x"], st["p"]
    c = _init(x, st["perm"], i, k=p["k"])
    first = None
    for _ in range(p["iters"]):
        c, a, j = km.iterate(x, c)
        first = first or (c, a, j)
    float(j)
    return first, p["iters"]


def window(st: dict, seconds: float, spans) -> dict:
    jobs, steps = [], 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with spans("fit_job"):
            first, n = _job(st, 1 + len(jobs))
        jobs.append(first)
        steps += n
    window_s = time.perf_counter() - t0
    st["jobs"] = jobs
    p = st["p"]
    return {"attempted": len(jobs), "failed": 0, "window_s": window_s,
            "e2e": {"lloyd_iter_ms": window_s * 1e3 / steps},
            "lloyd": {"n": p["n"], "k": p["k"], "d": p["d"],
                      "iterations": steps}}


def layer_record(st: dict, rec: dict) -> dict:
    return {}


def release(st: dict) -> None:
    del st["km"]


def check(st: dict, seed: int) -> dict[str, float]:
    """The first step of one job drawn from the seed against the
    reference step from the same initial centroids."""
    i = int(np.random.default_rng(seed).integers(len(st["jobs"])))
    x = st["x"]
    c0 = _init(x, st["perm"], 1 + i, k=st["p"]["k"])
    return compare.step_numbers(x, c0, st["jobs"][i],
                                ref_lloyd.assign(x, c0))


def _fault(kind: str, x, c0, step):
    """A first step with one fault planted: ``unchanged`` (the step returns
    the centroids it was given), ``half`` (the means taken over the first
    half of the points), ``altered`` (one returned centroid moved where
    it is produced)."""
    c, a, j = step
    if kind == "unchanged":
        return c0, a, j
    if kind == "half":
        h = x.shape[0] // 2
        mean, cnt = ref_lloyd.cluster_means(x[:h], a[:h], c.shape[0])
        return jnp.where((cnt > 0)[:, None], mean, c), a, j
    if kind == "altered":
        return c.at[0, 0].add(1.0), a, j
    raise ValueError(f"unknown fault {kind!r}")


def control(cell: dict, seed: int, precision: str,
            fault: str | None = None) -> dict[str, float]:
    """The reference step at ``precision`` put in the program's place,
    judged by the same numbers: the control of a limit. With ``fault``,
    the full-precision step with that fault planted."""
    p = cell["workload"]["params"]
    x, perm = _data(p, seed)
    c0 = _init(x, perm, 1, k=p["k"])
    step = ref_lloyd.step(x, c0, "highest" if fault else precision)
    if fault:
        step = _fault(fault, x, c0, step)
    return compare.step_numbers(x, c0, step, ref_lloyd.assign(x, c0))
