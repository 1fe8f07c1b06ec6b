"""Worker run in a subprocess with 4 fake CPU devices: the cell
``fit_dp4_k1024_d128`` at a CPU size (n=4096, k=16, d=32, 3 steps a job),
``bench/drivers/fit_jobs_sharded.py`` and the sharded reference.

    python _dp4_worker.py

Prints one JSON object per line, ``{"check": name, ...}``, read by
``test_bench_dp4.py``: whole runs (``bench.run.execute``, device check
skipped), sound and with each of three faults planted under the sharded
step; the reference's per-shard numbers beside ``compare.step_numbers``
on the gathered arrays; the data and initial centroids beside
``bench.data.blobs`` and ``fit_jobs``'; the traced window's counters; the
control at the configuration's width.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import copy  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import cells, compare, control, data, run  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.drivers import fit_jobs  # noqa: E402
from bench.drivers import fit_jobs_sharded as drv  # noqa: E402
from bench.reference import lloyd as ref_lloyd  # noqa: E402
from bench.reference import lloyd_sharded as ref  # noqa: E402
from repro.core import kmeans as kmeans_mod  # noqa: E402
from repro.kernels import ops  # noqa: E402

NAME = "fit_dp4_k1024_d128"
TINY = {"n": 4096, "k": 16, "d": 32, "iters": 3}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 4}
SEEDS = (2147483701, 4294967311)


def emit(check: str, **kw) -> None:
    print(json.dumps(dict(kw, check=check)), flush=True)


def tiny_cell() -> dict:
    cell = copy.deepcopy(cells.load_cell(NAME))
    cell["workload"]["params"].update(TINY)
    return cell


def execute(seed: int) -> dict:
    return run.execute(tiny_cell(), seed, 0.3, False,
                       t0=time.perf_counter(), device=dict(DEVICE))


def unchanged():
    return {"finalize_centroids": lambda s, cnt, c: c}


def half():
    orig = kmeans_mod.lloyd_stats

    def stats(x, c, cfg, blk=None):
        a, _s, _cnt, j = orig(x, c, cfg, blk)
        h, k = x.shape[0] // 2, c.shape[0]
        s = jnp.zeros((k, x.shape[1]), jnp.float32).at[a[:h]].add(x[:h])
        cnt = jnp.zeros((k,), jnp.float32).at[a[:h]].add(1.0)
        return a, s, cnt, j
    return {"lloyd_stats": stats}


def altered():
    orig = ops.finalize_centroids

    def finalize(s, cnt, c):   # moves the centroid of the largest cluster
        return orig(s, cnt, c).at[jnp.argmax(cnt), 0].add(1.0)
    return {"finalize_centroids": finalize}


def planted(fault):
    patches = fault()
    mods = {"finalize_centroids": ops, "lloyd_stats": kmeans_mod}
    saved = {n: getattr(mods[n], n) for n in patches}
    for n, f in patches.items():
        setattr(mods[n], n, f)
    try:
        return execute(SEEDS[0])
    finally:
        for n, f in saved.items():
            setattr(mods[n], n, f)


def main() -> None:
    assert len(jax.devices()) == 4, jax.devices()
    for seed in SEEDS:
        out = execute(seed)
        emit("sound", seed=seed, correct=out["correct"],
             checks=out["checks"], metrics=sorted(out["metrics"]))
    for fault in (unchanged, half, altered):
        out = planted(fault)
        emit("fault", fault=fault.__name__, correct=out["correct"],
             checks=out["checks"])

    # the cell's data and initial centroids against the one-chip cell's
    cell = tiny_cell()
    p = cell["workload"]["params"]
    st = drv.setup(cell, SEEDS[0], tr.Spans())
    x, perm = st["x"], st["perm"]
    key = jax.random.fold_in(data.base_key(SEEDS[0]), 1)
    chunk = drv.blobs(st["mesh"], key, p["n"], p["d"], p["k"])[1]
    x1 = data.blobs(key, p["n"], p["d"], p["k"], chunk=chunk)
    emit("data", chunk=chunk, equal=bool(np.array_equal(np.asarray(x),
                                                        np.asarray(x1))),
         shards=len(x.addressable_shards),
         shard_rows=sorted({s.data.shape[0] for s in x.addressable_shards}))
    xg, permg = jnp.asarray(np.asarray(x)), jnp.asarray(np.asarray(perm))
    emit("init", equal=all(
        np.array_equal(np.asarray(drv._c0(st, i)),
                       np.asarray(fit_jobs._init(xg, permg, i, k=p["k"])))
        for i in range(4)))

    # per-shard numbers against step_numbers on the gathered arrays, for
    # the program's first step and for a step with a fault planted
    first, _ = drv._job(st, 1)
    c0 = drv._c0(st, 1)
    steps = {"program": first,
             "altered": drv._fault("altered", x, c0, first),
             "half": drv._fault("half", x, c0, first)}
    a_ref = ref.assign(x, c0)
    whole_ref = ref_lloyd.assign(xg, jnp.asarray(np.asarray(c0)))
    for name, (c, a, j) in steps.items():
        sharded = ref.numbers(x, c0, (c, a, j), a_ref)
        whole = compare.step_numbers(
            xg, jnp.asarray(np.asarray(c0)),
            (jnp.asarray(np.asarray(c)), jnp.asarray(np.asarray(a)), j),
            whole_ref)
        emit("numbers", step=name, sharded=sharded, whole=whole)

    # a traced window records the program's two counters
    spans = tr.Spans(tracing=True)
    rec = drv.window(st, 0.05, spans)
    emit("counters", layer_record=drv.layer_record(st, rec),
         steps=rec["lloyd"]["iterations"], chips=rec["lloyd"]["chips"],
         psum_bytes=st["km"].pctx.collective_bytes(
             "stats_psum", k=p["k"], d=p["d"]))

    # the control at the configuration's width moves near-ties
    cell = tiny_cell()
    cell["workload"]["params"].update(n=65536, k=256, d=128)
    for seed in (11, 12, 13):
        r = control.readings(cell, seed)
        emit("control", seed=seed, numbers=r["numbers"])


if __name__ == "__main__":
    main()
