"""Worker run in a subprocess with 4 fake CPU devices: ``KMeans(cfg, mesh)``.

    python _dp_worker.py results.npz   # the arrays test_dp_kmeans.py checks
    python _dp_worker.py --obs         # tracing of the sharded step (JSON)

Seeded blobs, n=4096, k=16, d=32, on a 4 x 1 (data x model) mesh. The
first form saves, per step impl (fused, two-pass): the sharded step from
a host array and one device's step from the same centroids, the plain
reference step (``bench/reference/lloyd.py``), the sharded fit and
``make_distributed_kmeans`` from the same key; the sharded random init
and ``random_init`` for four keys, and the init program's collectives;
the K-sharded step (2 x 2 mesh) and the sharded ``predict``. The second
prints one JSON object: the program's counters after sharded steps with
tracing on and off, and the scopes in the lowered step.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.core import KMeans, KMeansConfig, random_init  # noqa: E402
from repro.core.distributed import make_distributed_kmeans  # noqa: E402
from repro.core.parallel import ParallelContext, build_mesh  # noqa: E402

N, K, D = 4096, 16, 32


def blobs(seed: int):
    kc, ka, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
    centers = jax.random.normal(kc, (K, D)) * 5.0
    lbl = jax.random.randint(ka, (N,), 0, K)
    return np.asarray(centers[lbl] + 0.4 * jax.random.normal(kn, (N, D)))


def program(path: str) -> None:
    from bench.reference import lloyd as ref_lloyd
    assert len(jax.devices()) == 4, jax.devices()
    mesh = build_mesh((4, 1), ("data", "model"))
    x = blobs(0)
    c0 = np.asarray(random_init(jax.random.PRNGKey(1), x, K))
    out = {"x": x, "c0": c0}
    for impl in ("fused", "two_pass"):
        cfg = KMeansConfig(k=K, max_iters=6, tol=0.0, step_impl=impl)
        km = KMeans(cfg, mesh=mesh)
        c, a, j = km.iterate(x, c0)                # a host array, placed
        out[f"mesh_{impl}_c"], out[f"mesh_{impl}_a"] = c, a
        out[f"mesh_{impl}_j"] = j
        out[f"mesh_{impl}_a_spec"] = str(a.sharding.spec)
        out[f"mesh_{impl}_a_shards"] = len(a.addressable_shards)
        c, a, j = KMeans(cfg).iterate(x, c0)
        out[f"one_{impl}_c"], out[f"one_{impl}_a"] = c, a
        out[f"one_{impl}_j"] = j
        key = jax.random.PRNGKey(2)
        st = km.fit(key, x)
        pctx = km.pctx
        ref_fit = make_distributed_kmeans(mesh, cfg)
        c, _a, j = ref_fit(pctx.shard_points(x),
                           pctx.replicate(random_init(key, x, K)))
        out[f"fit_{impl}_c"], out[f"fit_{impl}_j"] = st.centroids, st.inertia
        out[f"fit_{impl}_iteration"] = st.iteration
        out[f"dist_{impl}_c"], out[f"dist_{impl}_j"] = c, j
    c, a, j = ref_lloyd.step(jnp.asarray(x), jnp.asarray(c0))
    out["ref_c"], out["ref_a"], out["ref_j"] = c, a, j

    km = KMeans(KMeansConfig(k=K), mesh=mesh)
    xs = km.pctx.shard_points(x)
    for seed in range(4):
        key = jax.random.PRNGKey(10 + seed)
        out[f"init_mesh_{seed}"] = km._init(key, xs)
        out[f"init_one_{seed}"] = random_init(key, x, K)
    hlo = km._init.lower(jax.random.PRNGKey(0), xs).compile().as_text()
    out["init_collectives"] = " ".join(sorted(re.findall(
        r"= [^\n]*? (all-reduce|all-gather|all-to-all|collective-permute|"
        r"reduce-scatter)\(", hlo)))
    out["predict_mesh"] = km.predict(x, c0)
    out["predict_one"] = KMeans(KMeansConfig(k=K)).predict(x, c0)

    cfg = KMeansConfig(k=K, step_impl="two_pass")
    km2 = KMeans(cfg, mesh=build_mesh((2, 2), ("data", "model")))
    c, a, j = km2.iterate(x, c0)
    out["kshard_axis"] = str(km2.pctx.k_axis)
    out["kshard_c"], out["kshard_a"], out["kshard_j"] = c, a, j
    np.savez(path, **{k: np.asarray(v) for k, v in out.items()})


def tracing() -> None:
    mesh = build_mesh((4, 1), ("data", "model"))
    x = blobs(3)
    c0 = x[:K]
    cfg = KMeansConfig(k=K)
    km = KMeans(cfg, mesh=mesh)
    obs.reset()
    km.iterate(x, c0)                       # off: nothing recorded
    off = obs.snapshot()
    obs.enable()
    c = c0
    for _ in range(3):
        c, _a, _j = km.iterate(x, c)
    KMeans(cfg).iterate(x, c0)              # one device: not counted
    on = obs.snapshot()
    obs.disable()
    text = km._step.lower(km._points(x), km._centroids(c0)).as_text(
        debug_info=True)
    print(json.dumps({
        "off": off, "counters": on["counters"], "spans": len(on["spans"]),
        "psum_bytes": km.pctx.collective_bytes("stats_psum", k=K, d=D),
        "scopes": sorted(set(re.findall(
            r"lloyd\.(?:fused|assign|update|finalize|allreduce)\b", text)))}))


if __name__ == "__main__":
    if sys.argv[1] == "--obs":
        tracing()
    else:
        program(sys.argv[1])
