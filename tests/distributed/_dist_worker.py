"""Worker executed in a subprocess with 8 fake CPU devices.

Checks (each prints PASS/FAIL lines parsed by the pytest wrapper):
  1. distributed kmeans (2x4 mesh, N-sharded) == single-device kmeans
  2. fused FlashLloyd step distributed (step_impl="fused") == reference
  3. K-sharded (model-axis) kmeans == plain kmeans (incl. with a fused
     config, which transparently uses the stats-only sort-inverse pass)
  4. compressed cross-pod reduction converges to ~the same inertia
  5. sharded train_step == single-device train_step (grad equivalence)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import (KMeansConfig, build_mesh, init_centroids,
                        make_kmeans_fn)
from repro.core.distributed import make_distributed_kmeans

ok = True


def check(name, cond, detail=""):
    global ok
    print(("PASS" if cond else "FAIL"), name, detail, flush=True)
    ok = ok and bool(cond)


def main():
    global ok
    assert len(jax.devices()) == 8, jax.devices()
    key = jax.random.PRNGKey(0)
    n, k, d = 1024, 16, 8
    x = jax.random.normal(key, (n, d))
    c0 = init_centroids(jax.random.PRNGKey(1), x, k, "random")
    cfg = KMeansConfig(k=k, max_iters=8, tol=-1.0)

    # single-device reference loop (same fixed iteration count)
    from repro.core.kmeans import lloyd_step
    c_ref = c0
    for _ in range(cfg.max_iters):
        c_ref, a_ref, j_ref = lloyd_step(x, c_ref, cfg)

    # --- 1. N-sharded over a (2,4) mesh ----------------------------------
    mesh = build_mesh((2, 4), ("pod", "data"))
    fit = make_distributed_kmeans(mesh, cfg, data_axes=("pod", "data"))
    xs = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"), None)))
    c0r = jax.device_put(c0, NamedSharding(mesh, P(None, None)))
    c_dist, a_dist, j_dist = fit(xs, c0r)
    check("n_sharded_centroids",
          np.allclose(np.asarray(c_dist), np.asarray(c_ref), atol=1e-4),
          f"max_err={np.abs(np.asarray(c_dist)-np.asarray(c_ref)).max():.2e}")
    check("n_sharded_inertia",
          abs(float(j_dist) - float(j_ref)) / float(j_ref) < 1e-5)

    # --- 2. fused FlashLloyd step, N-sharded -------------------------------
    cfg_fused = KMeansConfig(k=k, max_iters=8, tol=-1.0, step_impl="fused")
    fitf = make_distributed_kmeans(mesh, cfg_fused,
                                   data_axes=("pod", "data"))
    cf, af, jf = fitf(xs, c0r)
    check("n_sharded_fused_centroids",
          np.allclose(np.asarray(cf), np.asarray(c_ref), atol=1e-4),
          f"max_err={np.abs(np.asarray(cf)-np.asarray(c_ref)).max():.2e}")
    check("n_sharded_fused_inertia",
          abs(float(jf) - float(j_ref)) / float(j_ref) < 1e-5)

    # --- 3. K-sharded (2-D kmeans) ----------------------------------------
    mesh2 = build_mesh((2, 4), ("data", "model"))
    fit2 = make_distributed_kmeans(mesh2, cfg, data_axes=("data",),
                                   k_axis="model")
    xs2 = jax.device_put(x, NamedSharding(mesh2, P("data", None)))
    c02 = jax.device_put(c0, NamedSharding(mesh2, P("model", None)))
    c2, a2, j2 = fit2(xs2, c02)
    check("k_sharded_centroids",
          np.allclose(np.asarray(c2), np.asarray(c_ref), atol=1e-4),
          f"max_err={np.abs(np.asarray(c2)-np.asarray(c_ref)).max():.2e}")

    # fused-configured cfg on the K-sharded path: stats-only pass falls
    # back to sort-inverse — must not raise and must agree.
    fit2f = make_distributed_kmeans(mesh2, cfg_fused, data_axes=("data",),
                                    k_axis="model")
    c2f, _, _ = fit2f(xs2, c02)
    check("k_sharded_fused_cfg_centroids",
          np.allclose(np.asarray(c2f), np.asarray(c_ref), atol=1e-4),
          f"max_err={np.abs(np.asarray(c2f)-np.asarray(c_ref)).max():.2e}")

    # --- 4. compressed cross-pod EF reduction -----------------------------
    fit3 = make_distributed_kmeans(mesh, cfg, data_axes=("pod", "data"),
                                   compress_pod_axis="pod")
    c3, _, j3 = fit3(xs, c0r)
    rel = abs(float(j3) - float(j_ref)) / float(j_ref)
    check("compressed_pod_inertia_close", rel < 0.02, f"rel={rel:.4f}")

    # --- 5. sharded train step == single device ---------------------------
    from repro.configs.base import get_config
    from repro.launch import specs as SP
    from repro.models import model as M
    from repro.optim import adamw
    from repro.train.train_step import make_train_step

    acfg = get_config("llama3-8b").reduced()
    params, spec_tree = M.init_model(jax.random.PRNGKey(5), acfg,
                                     max_pos=64)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (4, 32), 0,
                                acfg.vocab_size)
    batch = {"tokens": tokens,
             "labels": jnp.roll(tokens, -1, 1).at[:, -1].set(-1)}

    # single-device
    step1 = make_train_step(acfg, None, compute_dtype=jnp.float32,
                            remat=False)
    opt = adamw.init(params)
    p1, o1, m1 = jax.jit(step1)(params, opt, batch,
                                jnp.zeros((), jnp.int32))

    # sharded on (2,4) data/model mesh
    p_sh = SP.resolve(spec_tree, params, mesh2)
    params_s = jax.tree_util.tree_map(jax.device_put, params, p_sh)
    opt_s = adamw.init(params_s)
    step2 = make_train_step(acfg, mesh2, compute_dtype=jnp.float32,
                            remat=False)
    batch_s = {k_: jax.device_put(
        v, NamedSharding(mesh2, P("data", None))) for k_, v in batch.items()}
    p2, o2, m2 = jax.jit(step2)(params_s, opt_s, batch_s,
                                jnp.zeros((), jnp.int32))
    check("sharded_loss_equal",
          abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3,
          f"{float(m1['loss'])} vs {float(m2['loss'])}")
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(jax.tree_util.tree_leaves(p1),
                              jax.tree_util.tree_leaves(p2)))
    check("sharded_params_equal", err < 5e-3, f"max_err={err:.2e}")

    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
