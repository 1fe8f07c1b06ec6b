"""Worker executed in a subprocess with 8 fake CPU devices: the
ParallelContext layer and sharded FlashIVF.

Checks (each prints PASS/FAIL lines parsed by the pytest wrapper):
  1. two-stage K-sharded assignment == single-device flash_assign
     *bitwise*, including ties broken toward the lower centroid id
  2. sharded IVFIndex.build/search on a (2 data x 4 cells) mesh returns
     identical ids to the single-device index at full nprobe (and at a
     partial nprobe on well-separated data)
  3. sharded add()/refresh() (stats through the psum tree) match the
     single-device online path; search stays id-identical afterwards
  4. ragged corpus / ragged batches: padding rows are masked out of
     every statistics reduction — no NaN, same centroids
  5. a K-shard owning only dead cells (zero points): finite centroids
     and top-k results, honest -1 ids only where the pool runs dry
  6. data-parallel StreamingKMeans.partial_fit == single-device
     (one O(K·d) psum per mini-batch; whole-shard padding tolerated)
  7. collective-bytes model: sharded search traffic is O(b·L) —
     linear in b and L, independent of cap/d/N (never the buckets)
  8. reliability: a snapshot taken on one mesh restores onto no mesh
     or a different mesh with identical results; an injected dead
     K-shard degrades to filtered brute force (finite, self-healing);
     injected NaN stats are repaired by guarded refresh in lockstep
     with the single-device index
  9. paged bucket store: sharded parity + elastic snapshots
 10. two-level router: coarse arrays replicate, fine cells stay
     partitioned; sharded routed search is id-identical to the
     single-device routed index (full *and* partial nprobe, fp32 and
     q8); a routed snapshot restores its router onto a different mesh
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import KMeansConfig
from repro.core.parallel import ParallelContext, build_mesh
from repro.core.streaming import StreamingKMeans
from repro.index import IVFIndex
from repro.kernels import ops

ok = True


def check(name, cond, detail=""):
    global ok
    print(("PASS" if cond else "FAIL"), name, detail, flush=True)
    ok = ok and bool(cond)


def main():
    assert len(jax.devices()) == 8, jax.devices()
    key = jax.random.PRNGKey(0)
    n, k, d = 4096, 64, 32
    kc, ka, kn, kq = jax.random.split(key, 4)
    centers = jax.random.normal(kc, (k, d)) * 5.0
    lbl = jax.random.randint(ka, (n,), 0, k)
    x = centers[lbl] + 0.4 * jax.random.normal(kn, (n, d))
    q = x[jax.random.randint(kq, (128,), 0, n)]

    mesh = build_mesh((2, 4), ("data", "model"))
    pctx = ParallelContext.for_mesh(mesh)
    check("logical_axes_resolved",
          pctx.data_axes == ("data",) and pctx.k_axis == "model",
          pctx.describe())

    # --- 1. two-stage assignment: bitwise parity + tie-breaking -----------
    cfg = KMeansConfig(k=k)
    assign = pctx.make_assign(cfg)
    a_ref, m_ref = ops.flash_assign(x, centers.astype(x.dtype))
    a_sh, m_sh = assign(pctx.shard_points(x), pctx.shard_centroids(centers))
    check("two_stage_assign_bitwise",
          np.array_equal(np.asarray(a_sh), np.asarray(a_ref)))
    check("two_stage_assign_dists",
          np.allclose(np.asarray(m_sh), np.asarray(m_ref), rtol=1e-6))
    # duplicated centroids: every point has >= 2 exactly-tied candidates
    # in *different* k-shards; the winner must be the lower global id
    cdup = jnp.concatenate([centers[: k // 2], centers[: k // 2]], 0)
    a_ref_t, _ = ops.flash_assign(x, cdup.astype(x.dtype))
    a_sh_t, _ = assign(pctx.shard_points(x), pctx.shard_centroids(cdup))
    check("two_stage_assign_tie_bitwise",
          np.array_equal(np.asarray(a_sh_t), np.asarray(a_ref_t))
          and int(np.max(np.asarray(a_sh_t))) < k // 2)

    # --- 2. sharded IVF build + search parity -----------------------------
    idx_ref = IVFIndex.build(x, k=k, max_iters=6)
    idx_sh = IVFIndex.build(x, k=k, max_iters=6, pctx=pctx)
    check("sharded_build_centroids",
          np.allclose(np.asarray(idx_ref.centroids),
                      np.asarray(idx_sh.centroids), atol=1e-5))
    topk = 10
    ids_ref, d_ref = idx_ref.search(q, topk=topk, nprobe=k)
    ids_sh, d_sh = idx_sh.search(q, topk=topk, nprobe=k)
    check("sharded_search_full_nprobe_ids_identical",
          np.array_equal(np.asarray(ids_sh), np.asarray(ids_ref)))
    check("sharded_search_full_nprobe_dists",
          np.allclose(np.asarray(d_sh), np.asarray(d_ref),
                      rtol=1e-5, atol=1e-5))
    ids_ref_p, _ = idx_ref.search(q, topk=topk, nprobe=8)
    ids_sh_p, _ = idx_sh.search(q, topk=topk, nprobe=8)
    check("sharded_search_partial_nprobe_ids_identical",
          np.array_equal(np.asarray(ids_sh_p), np.asarray(ids_ref_p)))

    # --- 3. online add + refresh through the psum tree --------------------
    kx, ky = jax.random.split(kq)
    x_new = centers[jax.random.randint(kx, (333,), 0, k)] \
        + 0.4 * jax.random.normal(ky, (333, d))
    a1 = idx_ref.add(x_new)
    a2 = idx_sh.add(x_new)          # 333 is ragged over 2 data shards
    check("sharded_add_assignments", np.array_equal(np.asarray(a1),
                                                    np.asarray(a2)))
    check("sharded_add_pending_stats",
          np.allclose(np.asarray(idx_ref._pending.sums),
                      np.asarray(idx_sh._pending.sums), atol=1e-3)
          and np.allclose(np.asarray(idx_ref._pending.counts),
                          np.asarray(idx_sh._pending.counts)))
    idx_ref.refresh()
    idx_sh.refresh()
    check("sharded_refresh_centroids",
          np.allclose(np.asarray(idx_ref.centroids),
                      np.asarray(idx_sh.centroids), atol=1e-4))
    ids_ref2, _ = idx_ref.search(q, topk=topk, nprobe=k)
    ids_sh2, _ = idx_sh.search(q, topk=topk, nprobe=k)
    check("sharded_search_after_add_ids_identical",
          np.array_equal(np.asarray(ids_sh2), np.asarray(ids_ref2)))

    # --- 4. ragged corpus build (N % shards != 0) -------------------------
    x_rag = x[:4001]
    idx_rag_ref = IVFIndex.build(x_rag, k=k, max_iters=4)
    idx_rag = IVFIndex.build(x_rag, k=k, max_iters=4, pctx=pctx)
    check("ragged_build_finite",
          bool(jnp.all(jnp.isfinite(idx_rag.centroids))))
    check("ragged_build_centroids",
          np.allclose(np.asarray(idx_rag_ref.centroids),
                      np.asarray(idx_rag.centroids), atol=1e-4))
    ids_rr, _ = idx_rag_ref.search(q, topk=topk, nprobe=k)
    ids_rs, drs = idx_rag.search(q, topk=topk, nprobe=k)
    check("ragged_build_search_ids_identical",
          np.array_equal(np.asarray(ids_rs), np.asarray(ids_rr)))

    # --- 5. a K-shard owning only dead cells ------------------------------
    # all points live in cells 0..k/2-1: the last two k-shards own only
    # empty posting lists and zero-count centroids
    lbl_lo = jax.random.randint(ka, (n,), 0, k // 2)
    x_lo = centers[lbl_lo] + 0.4 * jax.random.normal(kn, (n, d))
    dead = IVFIndex(centers, capacity=256, pctx=pctx)
    dead.add(x_lo)
    dead.refresh()
    check("dead_shard_refresh_finite",
          bool(jnp.all(jnp.isfinite(dead.centroids))))
    # dead cells had zero evidence: their centroids must be kept as-is
    check("dead_shard_centroids_kept",
          np.allclose(np.asarray(dead.centroids)[k // 2:],
                      np.asarray(centers)[k // 2:]))
    ids_d, dist_d = dead.search(q, topk=topk, nprobe=k)
    dead_ref = IVFIndex(centers, capacity=256)
    dead_ref.add(x_lo)
    dead_ref.refresh()
    ids_dr, _ = dead_ref.search(q, topk=topk, nprobe=k)
    check("dead_shard_search_ids_identical",
          np.array_equal(np.asarray(ids_d), np.asarray(ids_dr)))
    check("dead_shard_search_finite",
          bool(jnp.all(jnp.isfinite(dist_d)))
          and int(np.min(np.asarray(ids_d))) >= 0)
    # drain the pool below topk: only -1 ids may fill the tail
    tiny = IVFIndex(centers[:8], capacity=8, pctx=ParallelContext(
        build_mesh((2, 4), ("data", "model")), k_axis="model"))
    tiny.add(x_lo[:4])
    ids_t, dist_t = tiny.search(q[:16], topk=6, nprobe=8)
    valid = np.asarray(ids_t) >= 0
    check("dry_pool_honest_minus_one",
          bool(np.all(np.sum(valid, axis=1) == 4))
          and bool(np.all(np.isfinite(np.asarray(dist_t)[valid]))))

    # --- 6. data-parallel streaming partial_fit ---------------------------
    dctx = ParallelContext(build_mesh((8,), ("data",)))
    scfg = KMeansConfig(k=16, init="random")
    sk_ref = StreamingKMeans(scfg, seed=3)
    sk_par = StreamingKMeans(scfg, seed=3, pctx=dctx)
    for lo, hi in ((0, 512), (512, 1029), (1029, 1329), (1329, 2329)):
        sk_ref.partial_fit(x[lo:hi])    # ragged batch sizes
        sk_par.partial_fit(x[lo:hi])
    check("parallel_partial_fit_centroids",
          np.allclose(np.asarray(sk_ref.centroids),
                      np.asarray(sk_par.centroids), atol=1e-4))
    check("parallel_partial_fit_counts",
          np.allclose(np.asarray(sk_ref.stats.counts),
                      np.asarray(sk_par.stats.counts), atol=1e-3))
    sk_par.partial_fit(x[:3])   # 5 of 8 shards are pure padding
    check("parallel_partial_fit_tiny_batch_finite",
          bool(jnp.all(jnp.isfinite(sk_par.centroids))))

    # --- 6b. tol early-stop parity with the single-device rule ------------
    # a huge tol stops the while_loop after the first M-step, in both
    # the N-sharded and the K-sharded (psum'd scalar shift) loops
    c0 = centers + 0.1
    one = KMeansConfig(k=k, max_iters=1, tol=-1.0)
    lax_ = KMeansConfig(k=k, max_iters=8, tol=1e9)
    for name, kw in (("n_sharded", dict()),
                     ("k_sharded", dict(k_axis="model"))):
        pc = ParallelContext(build_mesh((2, 4), ("data", "model")), **kw)
        cs = pc.shard_centroids(c0)
        c_one = pc.make_kmeans_fit(one)(pc.shard_points(x), cs).centroids
        c_tol = pc.make_kmeans_fit(lax_)(pc.shard_points(x), cs).centroids
        check(f"tol_early_stop_{name}",
              np.array_equal(np.asarray(c_one), np.asarray(c_tol)))

    # --- 7. collective-bytes model: O(b·L), payload-free ------------------
    b0 = pctx.search_collective_bytes(128, 8, 10, k, cap=64, d=32)
    check("collective_bytes_payload_free",
          b0 == pctx.search_collective_bytes(128, 8, 10, k,
                                             cap=4096, d=1024))
    check("collective_bytes_linear_in_b",
          pctx.search_collective_bytes(256, 8, 10, k) == 2 * b0)
    ll, pk = min(8, k // 4), 4
    check("collective_bytes_value",
          b0 == 2 * 4 * 128 * (ll + 10) * pk, f"b0={b0}")
    # sanity: the sharded search moved less than the buckets it scanned
    payload = idx_sh.cap * d * 4 * 8
    check("collective_bytes_below_payload",
          pctx.search_collective_bytes(128, 8, 10, k) < 128 * payload)

    # --- 8. reliability: mesh-agnostic snapshots + sharded fault seams ----
    import tempfile

    from repro.kernels import ref as _ref
    from repro.reliability import (FaultEvent, FaultInjector, FaultPlan,
                                   corrupt_stats)

    with tempfile.TemporaryDirectory() as td:
        idx_sh.save(td, seqno=5)
        # a snapshot taken on the (2 data x 4 cells) mesh restores onto
        # no mesh at all...
        flat = IVFIndex.load(td)
        ids_f, _ = flat.search(q, topk=topk, nprobe=k)
        check("snapshot_restore_unsharded_ids_identical",
              np.array_equal(np.asarray(ids_f), np.asarray(ids_sh2)))
        # ...and onto a *different* (4 data x 2 cells) mesh
        pctx42 = ParallelContext(build_mesh((4, 2), ("data", "model")),
                                 k_axis="model")
        re42 = IVFIndex.load(td, pctx=pctx42)
        ids_42, _ = re42.search(q, topk=topk, nprobe=k)
        check("snapshot_restore_other_mesh_ids_identical",
              np.array_equal(np.asarray(ids_42), np.asarray(ids_sh2)))

    # dead-shard injection: blanking one K-shard out of both merges must
    # equal brute force over the surviving shards' buckets — degraded
    # honestly, never poisoned
    dead_shard = 2
    idx_sh.faults = FaultInjector(FaultPlan(
        [FaultEvent("search", "dead_shard", 0, arg=dead_shard)]))
    ids_dead, d_dead = idx_sh.search(q, topk=topk, nprobe=k)
    idx_sh.faults = None
    kl = k // pctx.n_k_shards
    bx, bi = idx_sh.store.dense()
    bx, bi = bx.copy(), bi.copy()
    bx[dead_shard * kl:(dead_shard + 1) * kl] = 1e15
    bi[dead_shard * kl:(dead_shard + 1) * kl] = -1
    qd = jnp.asarray(q, idx_sh.dtype)
    pos, _ = _ref.probe_ref(qd, jnp.asarray(bx.reshape(-1, d)), topk)
    ids_exp = jnp.take(jnp.asarray(bi.reshape(-1)), pos)
    check("dead_shard_injection_matches_filtered_brute",
          np.array_equal(np.asarray(ids_dead), np.asarray(ids_exp)))
    check("dead_shard_injection_finite",
          bool(jnp.all(jnp.isfinite(d_dead))))
    ids_back, _ = idx_sh.search(q, topk=topk, nprobe=k)   # next call heals
    check("dead_shard_recovers_next_call",
          np.array_equal(np.asarray(ids_back), np.asarray(ids_sh2)))

    # nan_stats injection on the sharded add path: the same seeded
    # corruption applied to the single-device index, both guarded
    # refreshes repair, centroids stay in lockstep
    nan_seed = 9
    x_nan = centers[jax.random.randint(kx, (256,), 0, k)] \
        + 0.4 * jax.random.normal(ky, (256, d))
    idx_sh.faults = FaultInjector(FaultPlan(
        [FaultEvent("add", "nan_stats", 0, arg=nan_seed)]))
    a_sh_n = idx_sh.add(x_nan)
    idx_sh.faults = None
    a_ref_n = idx_ref.add(x_nan)
    idx_ref._pending, _ = corrupt_stats(idx_ref._pending, nan_seed)
    check("nan_stats_sharded_add_assignments",
          np.array_equal(np.asarray(a_sh_n), np.asarray(a_ref_n)))
    check("nan_stats_pending_corrupted",
          bool(jnp.any(jnp.isnan(idx_sh._pending.sums))))
    idx_ref.refresh(guard=True)
    idx_sh.refresh(guard=True)
    check("nan_stats_guarded_refresh_repairs",
          idx_sh.repaired_cells > 0
          and bool(jnp.all(jnp.isfinite(idx_sh.centroids))))
    check("nan_stats_guarded_refresh_parity",
          np.allclose(np.asarray(idx_ref.centroids),
                      np.asarray(idx_sh.centroids), atol=1e-4))
    ids_ref3, _ = idx_ref.search(q, topk=topk, nprobe=k)
    ids_sh3, _ = idx_sh.search(q, topk=topk, nprobe=k)
    check("nan_stats_search_after_repair_ids_identical",
          np.array_equal(np.asarray(ids_sh3), np.asarray(ids_ref3)))

    # --- 9. paged bucket store: sharded parity + elastic snapshots --------
    # the page pool + tables are sharded over the cells axis; results must
    # stay id-identical to the single-device padded index, and a snapshot
    # taken on the mesh must restore the *paged* store off-mesh bitwise
    pgd = IVFIndex(centers, capacity=256, pctx=pctx, store="paged")
    pgd.add(x)
    pgd_ref = IVFIndex(centers, capacity=256)
    pgd_ref.add(x)
    ids_pg, d_pg = pgd.search(q, topk=topk, nprobe=k)
    ids_pr, _ = pgd_ref.search(q, topk=topk, nprobe=k)
    check("paged_sharded_search_full_nprobe_ids_identical",
          np.array_equal(np.asarray(ids_pg), np.asarray(ids_pr)))
    check("paged_sharded_search_finite",
          bool(jnp.all(jnp.isfinite(d_pg))))
    ids_pg8, _ = pgd.search(q, topk=topk, nprobe=8)
    ids_pr8, _ = pgd_ref.search(q, topk=topk, nprobe=8)
    check("paged_sharded_search_partial_nprobe_ids_identical",
          np.array_equal(np.asarray(ids_pg8), np.asarray(ids_pr8)))
    pgd.add(x_new)
    pgd_ref.add(x_new)
    pgd.refresh()
    pgd_ref.refresh()
    ids_pg2, _ = pgd.search(q, topk=topk, nprobe=k)
    ids_pr2, _ = pgd_ref.search(q, topk=topk, nprobe=k)
    check("paged_sharded_add_refresh_ids_identical",
          np.array_equal(np.asarray(ids_pg2), np.asarray(ids_pr2)))
    with tempfile.TemporaryDirectory() as td:
        pgd.save(td, seqno=1)
        flat_pg = IVFIndex.load(td)
        check("paged_snapshot_restores_paged_store",
              flat_pg.store.kind == "paged")
        ids_fp, _ = flat_pg.search(q, topk=topk, nprobe=k)
        check("paged_snapshot_restore_unsharded_ids_identical",
              np.array_equal(np.asarray(ids_fp), np.asarray(ids_pg2)))

    # --- 10. two-level router: sharded parity + mesh-elastic snapshots ----
    # the coarse centroids / member table replicate (zero wire bytes for
    # routing); fine cells stay partitioned; the cross-shard probe merge
    # keys on candidate-axis position, so even *partial*-nprobe routed
    # probe lists match the single-device router entry for entry
    rt = IVFIndex.build(x, k=k, max_iters=6, router="two_level")
    check("routed_router_trained",
          rt.router.kind == "two_level" and rt.router.coarse_k < k)
    ids_rt, _ = rt.search(q, topk=topk, nprobe=k)
    # the router's exactness claim: routing loses nothing vs a flat
    # probe over the same store (brute force orders near-ties a few
    # float32 ulps differently than the bucket scan, so brute parity
    # is pinned on tie-free data in tests/index/test_router.py)
    rt_flat = IVFIndex(rt.centroids, capacity=rt.store.capacity,
                       store=rt.store)
    ids_fl, _ = rt_flat.search(q, topk=topk, nprobe=k)
    check("routed_full_nprobe_matches_flat",
          np.array_equal(np.asarray(ids_rt), np.asarray(ids_fl)))
    ids_rt8, _ = rt.search(q, topk=topk, nprobe=8)
    with tempfile.TemporaryDirectory() as td:
        rt.save(td, seqno=3)
        rt24 = IVFIndex.load(td, pctx=pctx)            # (2 data x 4 cells)
        check("routed_snapshot_mesh_restores_router",
              rt24.router.kind == "two_level"
              and np.array_equal(np.asarray(rt24.router.owner),
                                 np.asarray(rt.router.owner)))
        ids_24, _ = rt24.search(q, topk=topk, nprobe=k)
        check("routed_sharded_full_nprobe_ids_identical",
              np.array_equal(np.asarray(ids_24), np.asarray(ids_rt)))
        ids_248, _ = rt24.search(q, topk=topk, nprobe=8)
        check("routed_sharded_partial_nprobe_ids_identical",
              np.array_equal(np.asarray(ids_248), np.asarray(ids_rt8)))
        rt42 = IVFIndex.load(td, pctx=ParallelContext(
            build_mesh((4, 2), ("data", "model")), k_axis="model"))
        ids_428, _ = rt42.search(q, topk=topk, nprobe=8)
        check("routed_other_mesh_partial_nprobe_ids_identical",
              np.array_equal(np.asarray(ids_428), np.asarray(ids_rt8)))
    # quantized payloads route identically too (propose + rescore both
    # run over the routed probe list); add/refresh lockstep and the
    # partial-nprobe q8 case are covered single-device in
    # tests/index/test_router.py — this worker pins the cross-shard
    # merge at full coverage
    q8r_ref = IVFIndex(centers, capacity=256, codec="q8",
                       router="two_level")
    q8r_sh = IVFIndex(centers, capacity=256, pctx=pctx, codec="q8",
                      router="two_level")
    q8r_ref.add(x)
    q8r_sh.add(x)
    i_r, _ = q8r_ref.search(q, topk=topk, nprobe=k)
    i_s, _ = q8r_sh.search(q, topk=topk, nprobe=k)
    check("routed_q8_sharded_full_nprobe_ids_identical",
          np.array_equal(np.asarray(i_s), np.asarray(i_r)))

    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
