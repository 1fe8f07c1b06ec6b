"""ParallelContext layer + sharded FlashIVF tests.

Two tiers:
- multi-device equivalences run in a subprocess with 8 fake CPU devices
  (``_parallel_worker.py``; the main test process must keep seeing
  exactly 1 device) — marked slow, run explicitly by CI;
- single-device invariants (mesh helpers, logical-axis rules, the
  collective-bytes model, and the "zero shard_map call sites outside
  core/parallel.py" architecture guard) run in-process in tier-1;
- the in-process multi-device tests (``test_inprocess_*``) skip on one
  device; tier-1 runs them in a child pytest on 8 fake CPU devices.
"""
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(ROOT, "src", "repro")


@pytest.mark.slow
def test_parallel_layer_equivalences():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"   # the worker's fake devices are CPUs
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "distributed", "_parallel_worker.py")],
        capture_output=True, text=True, env=env, timeout=1500)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr[-4000:])
    assert r.returncode == 0, "parallel worker failed"
    assert "FAIL" not in r.stdout
    assert r.stdout.count("PASS") >= 50


# ---------------------------------------------------------------------------
# single-device invariants (tier-1)
# ---------------------------------------------------------------------------

def _py_sources():
    for dirpath, _, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def test_zero_shard_map_call_sites_outside_parallel():
    """The acceptance invariant of the ParallelContext refactor: the raw
    shard_map mechanism (jax.shard_map / jax.experimental.shard_map) is
    invoked in exactly one module. Drivers compose
    programs via ``ParallelContext.spmd`` and the ``make_*`` builders."""
    bare_call = re.compile(r"(?<![.\w])shard_map\s*\(")
    offenders = []
    for path in _py_sources():
        rel = os.path.relpath(path, SRC)
        if rel == os.path.join("core", "parallel.py"):
            continue
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if ("jax.shard_map" in code
                        or "experimental.shard_map" in code
                        or bare_call.search(code)):
                    offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, "\n".join(offenders)


def test_logical_axis_rules_have_points_and_cells():
    from repro.utils.sharding import DEFAULT_RULES
    assert DEFAULT_RULES["points"] == ("pod", "data")
    assert DEFAULT_RULES["cells"] == ("model",)


def test_parse_mesh_flag_and_build_mesh():
    from repro.core.parallel import build_mesh, parse_mesh_flag
    m = parse_mesh_flag("1x1")
    assert m.axis_names == ("data", "model")
    assert dict(m.shape) == {"data": 1, "model": 1}
    assert dict(parse_mesh_flag("1").shape) == {"data": 1, "model": 1}
    with pytest.raises(ValueError):
        parse_mesh_flag("1x2x3")
    with pytest.raises(ValueError):
        build_mesh((1, 1), ("data",))


def test_for_mesh_resolves_logical_axes_single_device():
    from repro.core.parallel import ParallelContext, build_mesh
    pctx = ParallelContext.for_mesh(build_mesh((1, 1), ("data", "model")))
    assert pctx.data_axes == ("data",)
    assert pctx.k_axis is None          # size-1 cells axis degrades
    assert pctx.n_data_shards == 1 and pctx.n_k_shards == 1


def test_parallel_context_validation():
    from repro.core.parallel import ParallelContext, build_mesh
    mesh = build_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError):
        ParallelContext(mesh, data_axes=("nope",))
    with pytest.raises(ValueError):
        ParallelContext(mesh, data_axes=("data",), k_axis="nope")
    with pytest.raises(ValueError):
        ParallelContext(mesh, data_axes=("data", "model"), k_axis="model")
    with pytest.raises(ValueError):
        ParallelContext(mesh).collective_bytes("nope")


def test_collective_bytes_model_single_device():
    """The wire-byte model itself is mesh-shape arithmetic — checkable
    on one device. O(b·L): linear in b and in the list lengths,
    independent of cap/d/N; stats psum is O(K·d) and N-free; a 1-way
    partition moves nothing."""
    from repro.core.parallel import (ParallelContext, build_mesh,
                                     search_collective_bytes_model)
    pctx = ParallelContext(build_mesh((1, 1), ("data", "model")),
                           k_axis="model")
    sp = pctx.collective_bytes("stats_psum", k=64, d=32)
    assert sp == 2 * 4 * (64 * 32 + 64 + 1)
    # degenerate 1-way partition: no cross-shard traffic at all
    assert pctx.search_collective_bytes(128, 8, 10, 64) == 0
    # hypothetical 8-way partition: O(b·L), linear in b, k-capped probe
    b1 = search_collective_bytes_model(128, 8, 10, 64, 8)
    assert b1 == 2 * 4 * 128 * (8 + 10) * 8
    assert search_collective_bytes_model(256, 8, 10, 64, 8) == 2 * b1
    assert search_collective_bytes_model(128, 1000, 10, 64, 8) == \
        search_collective_bytes_model(128, 8, 10, 64, 8)  # ll caps at K/P


def test_unsharded_index_reports_zero_collective_bytes(key):
    import jax
    from repro.index import IVFIndex
    x = jax.random.normal(key, (256, 16))
    idx = IVFIndex.build(x, k=8, max_iters=2)
    assert idx.search_collective_bytes(32, 10, 4) == 0


# ---------------------------------------------------------------------------
# in-process multi-device tests — run where
# XLA_FLAGS=--xla_force_host_platform_device_count=8 is set (the CI leg,
# and tier-1's child run below); self-skip on one device
# ---------------------------------------------------------------------------

def _require_devices(n: int):
    import jax
    if len(jax.devices()) < n:
        pytest.skip(f"needs >= {n} devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def test_inprocess_two_stage_assign_bitwise():
    _require_devices(8)
    import jax
    import numpy as np
    from repro.core import KMeansConfig
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.kernels import ops
    k, d = 16, 8
    key = jax.random.PRNGKey(0)
    c = jax.random.normal(key, (k, d)) * 3.0
    x = jax.random.normal(jax.random.fold_in(key, 1), (512, d))
    pctx = ParallelContext.for_mesh(build_mesh((2, 4), ("data", "model")))
    a_ref, _ = ops.flash_assign(x, c)
    a_sh, _ = pctx.make_assign(KMeansConfig(k=k))(
        pctx.shard_points(x), pctx.shard_centroids(c))
    assert np.array_equal(np.asarray(a_sh), np.asarray(a_ref))


def test_inprocess_sharded_search_ids_identical():
    _require_devices(8)
    import jax
    import numpy as np
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.index import IVFIndex
    key = jax.random.PRNGKey(0)
    kc, ka, kn, kq = jax.random.split(key, 4)
    k, d, n = 16, 8, 1024
    centers = jax.random.normal(kc, (k, d)) * 5.0
    x = centers[jax.random.randint(ka, (n,), 0, k)] \
        + 0.3 * jax.random.normal(kn, (n, d))
    q = x[jax.random.randint(kq, (64,), 0, n)]
    pctx = ParallelContext.for_mesh(build_mesh((2, 4), ("data", "model")))
    idx_ref = IVFIndex.build(x, k=k, max_iters=3)
    idx_sh = IVFIndex.build(x, k=k, max_iters=3, pctx=pctx)
    for nprobe in (4, k):
        ids_ref, _ = idx_ref.search(q, topk=10, nprobe=nprobe)
        ids_sh, _ = idx_sh.search(q, topk=10, nprobe=nprobe)
        assert np.array_equal(np.asarray(ids_sh), np.asarray(ids_ref)), \
            f"nprobe={nprobe}"


def test_inprocess_sharded_gathered_rows(tracing):
    """On a (2 data x 4 cells) mesh each K-shard gathers min(nprobe,
    K_local) owned lists of the gather width for every query of the
    data-padded batch: the ``ivf.gathered_rows`` counter counts them all."""
    _require_devices(8)
    import jax
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.index import IVFIndex
    k, d, n = 16, 8, 1024
    x = jax.random.normal(jax.random.PRNGKey(5), (n, d))
    pctx = ParallelContext.for_mesh(build_mesh((2, 4), ("data", "model")))
    idx = IVFIndex.build(x, k=k, max_iters=3, pctx=pctx)
    for nprobe in (2, k):
        tracing.reset()
        idx.search(x[:63], topk=10, nprobe=nprobe)   # b_pad = 64
        width = idx._gather_width(10, nprobe)
        assert tracing.snapshot()["counters"] == {
            "ivf.units": 1,
            "ivf.gathered_rows": 64 * min(nprobe, k // 4) * width * 4}


def test_inprocess_paged_store_sharded_ids_identical():
    """Paged bucket store on a (2 data x 4 cells) mesh: the page pool and
    page tables are sharded over the cells axis, yet search results stay
    id-identical to the single-device *padded* index — before and after
    an online add/refresh cycle."""
    _require_devices(8)
    import jax
    import numpy as np
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.index import IVFIndex
    key = jax.random.PRNGKey(3)
    kc, ka, kn, kq = jax.random.split(key, 4)
    k, d, n = 16, 8, 1024
    centers = jax.random.normal(kc, (k, d)) * 5.0
    x = centers[jax.random.randint(ka, (n,), 0, k)] \
        + 0.3 * jax.random.normal(kn, (n, d))
    q = x[jax.random.randint(kq, (64,), 0, n)]
    pctx = ParallelContext.for_mesh(build_mesh((2, 4), ("data", "model")))
    ref = IVFIndex(centers, capacity=128)
    sh = IVFIndex(centers, capacity=128, pctx=pctx, store="paged")
    assert sh.store.kind == "paged" and sh.store.n_shards == 4
    ref.add(x)
    sh.add(x)
    for nprobe in (4, k):
        ids_ref, _ = ref.search(q, topk=10, nprobe=nprobe)
        ids_sh, _ = sh.search(q, topk=10, nprobe=nprobe)
        assert np.array_equal(np.asarray(ids_sh), np.asarray(ids_ref)), \
            f"nprobe={nprobe}"
    x2 = centers[jax.random.randint(kq, (257,), 0, k)] \
        + 0.3 * jax.random.normal(kn, (257, d))
    ref.add(x2)
    sh.add(x2)
    ref.refresh()
    sh.refresh()
    ids_ref, _ = ref.search(q, topk=10, nprobe=k)
    ids_sh, _ = sh.search(q, topk=10, nprobe=k)
    assert np.array_equal(np.asarray(ids_sh), np.asarray(ids_ref))


def test_inprocess_q8_store_sharded_ids_identical():
    """Quantized (q8) bucket payloads on a (2 data x 4 cells) mesh: the
    int8 pools, scale sidecars and anchors shard over the cells axis,
    phase-1 proposals merge across shards (top-R + one O(b·R·d) row
    exchange), and the host-side exact rescore reproduces the
    single-device q8 index id-for-id — and brute force at full nprobe."""
    _require_devices(8)
    import jax
    import numpy as np
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.index import IVFIndex
    key = jax.random.PRNGKey(5)
    kc, ka, kn, kq = jax.random.split(key, 4)
    k, d, n = 16, 8, 1024
    centers = jax.random.normal(kc, (k, d)) * 5.0
    x = centers[jax.random.randint(ka, (n,), 0, k)] \
        + 0.3 * jax.random.normal(kn, (n, d))
    q = x[jax.random.randint(kq, (64,), 0, n)]
    pctx = ParallelContext.for_mesh(build_mesh((2, 4), ("data", "model")))
    for kind in ("padded", "paged"):
        ref = IVFIndex(centers, capacity=128, store=kind, codec="q8",
                       page_size=16)
        sh = IVFIndex(centers, capacity=128, pctx=pctx, store=kind,
                      codec="q8", page_size=16)
        assert sh.store.kind == kind and sh.codec_kind == "q8"
        ref.add(x)
        sh.add(x)
        for nprobe in (4, k):
            ids_ref, _ = ref.search(q, topk=10, nprobe=nprobe)
            ids_sh, _ = sh.search(q, topk=10, nprobe=nprobe)
            assert np.array_equal(np.asarray(ids_sh),
                                  np.asarray(ids_ref)), \
                f"{kind} nprobe={nprobe}"
        # full probe + sufficient R == brute force, up to near-tie swaps
        # (the rescore kernel and the brute reference accumulate f32
        # distances in different orders; same contract as test_ivf.py)
        ids_bf, d_bf = sh.search_brute(q, topk=10)
        ids_sh, d_sh = sh.search(q, topk=10, nprobe=k)
        ids_sh, ids_bf = np.asarray(ids_sh), np.asarray(ids_bf)
        d_sh, d_bf = np.asarray(d_sh), np.asarray(d_bf)
        np.testing.assert_allclose(d_sh, d_bf, rtol=1e-4, atol=1e-3)
        for r in range(ids_sh.shape[0]):
            for j in np.nonzero(ids_sh[r] != ids_bf[r])[0]:
                assert abs(d_sh[r, j] - d_bf[r, j]) <= 1e-3, (kind, r, j)
            assert set(ids_sh[r].tolist()) == set(ids_bf[r].tolist()), \
                (kind, r)
        # online mutation keeps the contract
        x2 = centers[jax.random.randint(kq, (257,), 0, k)] \
            + 0.3 * jax.random.normal(kn, (257, d))
        ref.add(x2)
        sh.add(x2)
        ref.refresh()
        sh.refresh()
        ids_ref, _ = ref.search(q, topk=10, nprobe=k)
        ids_sh, _ = sh.search(q, topk=10, nprobe=k)
        assert np.array_equal(np.asarray(ids_sh), np.asarray(ids_ref)), kind


def test_inprocess_q8_device_cache_sharded_parity():
    """Device rescore cache on a (2 data x 4 cells) mesh: the cache
    partitions over the cells axis next to the codes, hits substitute
    exact rows *before* the one-hot row exchange, and the sharded
    device-cache search is bitwise identical — ids AND dists — to both
    the single-device device-cache index and the sharded
    ``rescore="host"`` reservoir oracle, including after online add +
    refresh."""
    _require_devices(8)
    import jax
    import numpy as np
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.index import IVFIndex
    key = jax.random.PRNGKey(9)
    kc, ka, kn, kq = jax.random.split(key, 4)
    k, d, n = 16, 8, 1024
    centers = jax.random.normal(kc, (k, d)) * 5.0
    x = centers[jax.random.randint(ka, (n,), 0, k)] \
        + 0.3 * jax.random.normal(kn, (n, d))
    q = x[jax.random.randint(kq, (64,), 0, n)]
    pctx = ParallelContext.for_mesh(build_mesh((2, 4), ("data", "model")))
    for kind in ("padded", "paged"):
        kw = dict(capacity=128, store=kind, codec="q8", page_size=16)
        one = IVFIndex(centers, rescore="device", **kw)
        sh = IVFIndex(centers, pctx=pctx, rescore="device", **kw)
        oracle = IVFIndex(centers, pctx=pctx, rescore="host", **kw)
        assert sh.store.cache is not None
        assert sh.store.cache.shards == pctx.n_k_shards
        for idx in (one, sh, oracle):
            idx.add(x)
        for nprobe in (4, k):
            ids_1, d_1 = one.search(q, topk=10, nprobe=nprobe)
            ids_s, d_s = sh.search(q, topk=10, nprobe=nprobe)
            ids_h, d_h = oracle.search(q, topk=10, nprobe=nprobe)
            assert np.array_equal(np.asarray(ids_s), np.asarray(ids_1)), \
                f"{kind} nprobe={nprobe} vs single-device"
            assert np.array_equal(np.asarray(d_s), np.asarray(d_1)), \
                f"{kind} nprobe={nprobe} dists vs single-device"
            assert np.array_equal(np.asarray(ids_s), np.asarray(ids_h)), \
                f"{kind} nprobe={nprobe} vs host oracle"
            assert np.array_equal(np.asarray(d_s), np.asarray(d_h)), \
                f"{kind} nprobe={nprobe} dists vs host oracle"
        x2 = centers[jax.random.randint(kq, (257,), 0, k)] \
            + 0.3 * jax.random.normal(kn, (257, d))
        for idx in (one, sh, oracle):
            idx.add(x2)
            idx.refresh()
        ids_s, d_s = sh.search(q, topk=10, nprobe=k)
        ids_h, d_h = oracle.search(q, topk=10, nprobe=k)
        assert np.array_equal(np.asarray(ids_s), np.asarray(ids_h)), kind
        assert np.array_equal(np.asarray(d_s), np.asarray(d_h)), kind


def test_inprocess_dead_k_shard_is_robust():
    _require_devices(8)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.index import IVFIndex
    key = jax.random.PRNGKey(0)
    k, d = 16, 8
    centers = jax.random.normal(key, (k, d)) * 5.0
    # every point lands in the first half of the cells: the last two
    # K-shards own only dead cells
    lbl = jax.random.randint(jax.random.fold_in(key, 1), (512,), 0, k // 2)
    x = centers[lbl] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), (512, d))
    pctx = ParallelContext.for_mesh(build_mesh((2, 4), ("data", "model")))
    idx = IVFIndex(centers, capacity=128, pctx=pctx)
    idx.add(x)
    idx.refresh()
    assert bool(jnp.all(jnp.isfinite(idx.centroids)))
    np.testing.assert_allclose(np.asarray(idx.centroids)[k // 2:],
                               np.asarray(centers)[k // 2:], rtol=1e-6)
    ids, dists = idx.search(x[:32], topk=5, nprobe=k)
    assert bool(jnp.all(jnp.isfinite(dists)))
    assert int(np.min(np.asarray(ids))) >= 0


def test_inprocess_result_merge_breaks_ties_by_probe_order():
    """Construct an exact cross-shard distance tie where the cell probed
    *later* in global probe order is owned by the *lower*-rank shard:
    the merged result must still match the single-device tie-break
    (candidate-axis position = global probe rank), not shard rank."""
    _require_devices(2)
    import jax.numpy as jnp
    import numpy as np
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.index import IVFIndex
    # cells c0=(0,0) [shard 0], c1=(6,0) [shard 1]; points a=(3,-1e-3)
    # -> cell 0 and b=(7,0) -> cell 1; query q=(5,0):
    #   dist(q,a) = 4 + 1e-6 vs dist(q,b) = 4 ... not tied; use exact
    #   symmetric construction: a=(3,0) ties to c0/c1 but lands in c0
    #   (lower id), b=(7,0) in c1; dist(q,a) = dist(q,b) = 4 exactly,
    #   while probe order is [c1 (dist 1), c0 (dist 25)].
    centers = jnp.asarray([[0.0, 0.0], [6.0, 0.0]], jnp.float32)
    pts = jnp.asarray([[3.0, 0.0], [7.0, 0.0]], jnp.float32)
    q = jnp.asarray([[5.0, 0.0]], jnp.float32)
    ref = IVFIndex(centers, capacity=8)
    ref.add(pts)
    pctx = ParallelContext(build_mesh((1, 2), ("data", "model")),
                           k_axis="model")
    sh = IVFIndex(centers, capacity=8, pctx=pctx)
    sh.add(pts)
    ids_ref, d_ref = ref.search(q, topk=1, nprobe=2)
    ids_sh, d_sh = sh.search(q, topk=1, nprobe=2)
    # the tie winner is b (id 1): cell 1 is probed first, so b sits at
    # candidate position 0 in the single-device scan
    assert int(ids_ref[0, 0]) == 1
    assert np.array_equal(np.asarray(ids_sh), np.asarray(ids_ref))
    np.testing.assert_allclose(np.asarray(d_sh), np.asarray(d_ref))


def test_sharded_search_on_eight_cpu_devices():
    """The in-process tests above skip on one device, as tier-1 runs:
    run them in a child process on 8 fake CPU devices, so tier-1 covers
    the sharded search program on every router and codec."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "xdist", "-n", "2", "-k", "inprocess",
         os.path.abspath(__file__)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    tail = r.stdout[-4000:]
    assert r.returncode == 0, tail + r.stderr[-2000:]
    passed = re.search(r"(\d+) passed", tail)
    assert passed and int(passed.group(1)) >= 8 and "skipped" not in tail, \
        tail


def test_streaming_rejects_k_sharded_context():
    from repro.core import KMeansConfig
    from repro.core.parallel import ParallelContext, build_mesh
    from repro.core.streaming import StreamingKMeans
    pctx = ParallelContext(build_mesh((1, 1), ("data", "model")),
                           k_axis="model")
    with pytest.raises(ValueError):
        StreamingKMeans(KMeansConfig(k=4), pctx=pctx)
