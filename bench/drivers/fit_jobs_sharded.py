"""Traffic: back-to-back k-means fit jobs over points sharded across the
chips, one Lloyd step at a time.

Traffic parameters as ``fit_jobs``: ``n``, ``k``, ``d`` and ``iters``.
The points are the rows ``fit_jobs`` makes from the same seed (chunk
``i`` of the global array is ``bench.data.blobs``' chunk ``i``), made on
the devices shard by shard: each of the cell's ``chips`` devices draws
the chunks of its own rows, so none ever holds more than its shard and
one chunk. They are sharded along N over a ``chips x 1`` mesh and the
program is ``KMeans(cfg, mesh)``: each job runs ``iters`` steps through
``KMeans.iterate``, dispatched on the last one's centroids with no host
sync inside the job, and ends when its last inertia is on the host.
Set-up runs one job; the window starts jobs until ``--seconds`` have
passed and closes when the last one ends, as in ``fit_jobs``.

Each job starts from the ``k`` rows ``fit_jobs`` gives the same job (its
stretch of the same seeded permutation), each taken on the device that
holds it and combined by one psum, so no device gathers the points. The
check draws one job of the window from the seed and compares its first
step with the plain reference applied shard by shard
(``bench.reference.lloyd_sharded``).

With ``--trace 1`` the window records the program's counters
(``repro.obs``, enabled and reset at the window's start);
``layer_record`` returns ``lloyd.sharded_steps`` and
``lloyd.allreduce_bytes``.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import data
from bench.reference import lloyd_sharded as ref

AXIS = "data"
COUNTERS = ("lloyd.sharded_steps", "lloyd.allreduce_bytes")


def mesh_for(chips: int):
    from repro.core.parallel import build_mesh
    return build_mesh((chips, 1), (AXIS, "model"))


def _spmd(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.partial(jax.jit, static_argnames=("mesh", "n", "d", "n_centers",
                                             "chunk"))
def _blobs(key, *, mesh, n, d, n_centers, chunk):
    shards = mesh.shape[AXIS]
    per = n // shards // chunk

    def body(key):
        kc, kp = jax.random.split(key)
        centers = jax.random.normal(kc, (n_centers, d)) * 5.0
        first = jax.lax.axis_index(AXIS) * per

        def one(i):
            ka, kn = jax.random.split(jax.random.fold_in(kp, first + i))
            lbl = jax.random.randint(ka, (chunk,), 0, n_centers)
            return centers[lbl] + 0.4 * jax.random.normal(kn, (chunk, d))

        return jax.lax.map(one, jnp.arange(per)).reshape(per * chunk, d)

    return _spmd(body, mesh, P(), P(AXIS, None))(key)


def blobs(mesh, key, n: int, d: int, n_centers: int):
    """``bench.data.blobs(key, n, d, n_centers, chunk)`` sharded along N,
    made shard by shard; ``chunk`` is the returned second value (the
    largest divisor of the shard's rows up to ``blobs``' default)."""
    chunk = data._chunk(n // mesh.shape[AXIS], 1 << 18)
    x = _blobs(key, mesh=mesh, n=n, d=d, n_centers=n_centers, chunk=chunk)
    return x, chunk


@functools.partial(jax.jit, static_argnames=("mesh", "k"))
def _init(x, perm, job, *, mesh, k):
    """Job ``job``'s initial centroids: the ``k`` rows of its stretch of
    the permutation, each taken on its device, combined by one psum."""
    def body(xs, perm, job):
        n_loc = xs.shape[0]
        idx = jnp.take(perm, (job * k + jnp.arange(k)) % perm.shape[0])
        rel = idx - jax.lax.axis_index(AXIS) * n_loc
        own = (rel >= 0) & (rel < n_loc)
        rows = jnp.take(xs, jnp.clip(rel, 0, n_loc - 1), axis=0)
        return jax.lax.psum(jnp.where(own[:, None], rows, 0.0), AXIS)
    return _spmd(body, mesh, (P(AXIS, None), P(), P()), P(None, None))(
        x, perm, job)


def _data(p: dict, seed: int, mesh):
    key = data.base_key(seed)
    x, _chunk = blobs(mesh, jax.random.fold_in(key, 1), p["n"], p["d"],
                      p["k"])
    perm = jax.jit(lambda kp: jax.random.permutation(kp, p["n"]),
                   out_shardings=NamedSharding(mesh, P()))(
        jax.random.fold_in(key, 3))
    return x, perm


def setup(cell: dict, seed: int, spans) -> dict:
    from repro.core import KMeans, KMeansConfig
    p, cfg = cell["workload"]["params"], cell["config"]
    mesh = mesh_for(cell["workload"]["chips"])
    # the program first: one that cannot shard fails before the data
    km = KMeans(KMeansConfig(k=p["k"], max_iters=p["iters"], tol=cfg["tol"],
                             init=cfg["init"]), mesh=mesh)
    t = time.perf_counter()
    x, perm = _data(p, seed, mesh)
    jax.block_until_ready((x, perm))
    t_data = time.perf_counter() - t
    st = {"x": x, "perm": perm, "km": km, "p": p, "mesh": mesh,
          "counters": {}}
    t = time.perf_counter()
    _job(st, 0)
    st["setup_parts"] = {"data": t_data,
                         "warm-up job": time.perf_counter() - t}
    return st


def _c0(st: dict, i: int):
    return _init(st["x"], st["perm"], i, mesh=st["mesh"], k=st["p"]["k"])


def _job(st: dict, i: int):
    """Run job ``i``; returns its first step ``(centroids, assignments,
    inertia)`` and the number of steps."""
    km, x, p = st["km"], st["x"], st["p"]
    c = _c0(st, i)
    first = None
    for _ in range(p["iters"]):
        c, a, j = km.iterate(x, c)
        first = first or (c, a, j)
    float(j)
    return first, p["iters"]


def window(st: dict, seconds: float, spans) -> dict:
    from repro import obs
    if spans.tracing:
        obs.reset()
        obs.enable()
    jobs, steps = [], 0
    t0 = time.perf_counter()
    try:
        while time.perf_counter() - t0 < seconds:
            with spans("fit_job"):
                first, n = _job(st, 1 + len(jobs))
            jobs.append(first)
            steps += n
        window_s = time.perf_counter() - t0
    finally:
        if spans.tracing:
            st["counters"] = obs.snapshot()["counters"]
            obs.disable()
    st["jobs"] = jobs
    p = st["p"]
    return {"attempted": len(jobs), "failed": 0, "window_s": window_s,
            "e2e": {"lloyd_iter_ms": window_s * 1e3 / steps},
            "lloyd": {"n": p["n"], "k": p["k"], "d": p["d"],
                      "iterations": steps,
                      "chips": st["mesh"].shape[AXIS]}}


def layer_record(st: dict, rec: dict) -> dict:
    return {name: st["counters"].get(name) for name in COUNTERS}


def release(st: dict) -> None:
    del st["km"]


def check(st: dict, seed: int) -> dict[str, float]:
    """The first step of one job drawn from the seed against the
    reference step from the same initial centroids, shard by shard."""
    i = int(np.random.default_rng(seed).integers(len(st["jobs"])))
    x = st["x"]
    c0 = _c0(st, 1 + i)
    return ref.numbers(x, c0, st["jobs"][i], ref.assign(x, c0))


def _fault(kind: str, x, c0, step):
    """A first step with one fault planted: ``unchanged`` (the step returns
    the centroids it was given), ``half`` (the means taken over the first
    half of each device's points), ``altered`` (one returned centroid
    moved where it is produced)."""
    c, a, j = step
    if kind == "unchanged":
        return c0, a, j
    if kind == "half":
        mean, cnt = ref.cluster_means(x, a, c.shape[0], half=True)
        return np.where((cnt > 0)[:, None], mean, c), a, j
    if kind == "altered":
        c = np.array(c)
        c[0, 0] += 1.0
        return c, a, j
    raise ValueError(f"unknown fault {kind!r}")


def control(cell: dict, seed: int, precision: str,
            fault: str | None = None) -> dict[str, float]:
    """The sharded reference step at ``precision`` put in the program's
    place, judged by the same numbers: the control of a limit. With
    ``fault``, the full-precision step with that fault planted."""
    p = cell["workload"]["params"]
    mesh = mesh_for(cell["workload"]["chips"])
    x, perm = _data(p, seed, mesh)
    c0 = _init(x, perm, 1, mesh=mesh, k=p["k"])
    step = ref.step(x, c0, "highest" if fault else precision)
    if fault:
        step = _fault(fault, x, c0, step)
    return ref.numbers(x, c0, step, ref.assign(x, c0))
