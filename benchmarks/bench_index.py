"""FlashIVF search workload — the perf trajectory of the index subsystem.

Rows:
- ``ivf_build_*``: wall time of ``IVFIndex.build`` (train + invert);
  derived column reports points/s and the fitted posting-list capacity.
- ``ivf_search_*``: per-query-batch wall time at increasing nprobe;
  derived column reports recall@10 against the brute-force oracle and
  the modeled TPU time of the two fused stages (probe + grouped scan).
- ``ivf_add_*``: marginal wall cost of one online ``add`` batch +
  ``refresh`` (assign + CSR append + O(K·d) re-center) vs the modeled
  cost of refitting the whole index from scratch.
- ``ivf_search_sharded_*``: the sharded (cells-partitioned) search at
  increasing nprobe — wall QPS when the host exposes >1 device (run
  under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for the
  full two-stage path), plus the modeled per-batch cross-shard bytes
  from ``core.parallel`` (O(b·L): two (value, index) top-L merges —
  posting-list payloads never cross shards).

Wall numbers are compiled-XLA CPU / interpret-mode Pallas (relative
ordering only — see benchmarks/common.py); modeled numbers are the TPU
roofline.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.core import heuristics
from repro.index import IVFIndex, TwoLevelRouter, recall_at_k


def _blobs(key, n, k, d, spread=5.0, noise=0.4):
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (k, d)) * spread
    assign = jax.random.randint(ka, (n,), 0, k)
    return centers[assign] + jax.random.normal(kn, (n, d)) * noise


def rows() -> list[str]:
    out = []
    n, k, d, nq, topk = 20_000, 32, 32, 128, 10
    x = _blobs(jax.random.PRNGKey(0), n, k, d)
    q = x[jax.random.randint(jax.random.PRNGKey(1), (nq,), 0, n)]

    # --- build throughput -------------------------------------------------
    t0 = time.perf_counter()
    index = IVFIndex.build(x, k=k, max_iters=8)
    index.block_until_ready()
    us = (time.perf_counter() - t0) * 1e6
    out.append(C.fmt_row(
        f"ivf_build_N{n}_K{k}_d{d}", us,
        f"pts_per_s={n / (us / 1e6):.0f};cap={index.cap}"))

    # --- search QPS vs nprobe + recall@10 vs brute ------------------------
    ids_ref, _ = index.search_brute(q, topk=topk)
    for nprobe in (2, 8, k):
        us = C.wall_us(
            lambda qq, np_=nprobe: index.search(qq, topk=topk, nprobe=np_),
            q, reps=3, warmup=1)
        ids, _ = index.search(q, topk=topk, nprobe=nprobe)
        cand = nprobe * index.cap
        t_probe = C.modeled_time_s(
            C.assign_flops(nq, k, d),
            heuristics.probe_bytes_flash(nq, k, d, nprobe))
        t_scan = C.modeled_time_s(
            C.assign_flops(nq, cand, d),
            (nq * cand * d + 2 * nq * topk) * 4.0)
        out.append(C.fmt_row(
            f"ivf_search_nprobe{nprobe}_B{nq}", us,
            f"recall_at_{topk}={recall_at_k(ids, ids_ref):.3f};"
            f"modeled_tpu_us={(t_probe + t_scan) * 1e6:.1f}"))

    # --- quantized payloads: two-phase q8 search vs fp32 ------------------
    # wall QPS at matched recall, plus the planner's modeled scan-HBM
    # bytes per batch: the fp32 grouped scan streams cand*d*4 payload
    # bytes per query while q8 streams cand*(d*1 + 4) (int8 codes + f32
    # scale sidecar) and then rescores only R = rescore_mult*topk
    # candidate rows in exact fp32
    iq8 = IVFIndex.build(x, k=k, max_iters=8, codec="q8")
    iq8.block_until_ready()
    for nprobe in (2, 8, k):
        us = C.wall_us(
            lambda qq, np_=nprobe: iq8.search(qq, topk=topk, nprobe=np_),
            q, reps=3, warmup=1)
        ids, _ = iq8.search(q, topk=topk, nprobe=nprobe)
        cand = nprobe * iq8.cap
        r = min(max(topk, iq8.rescore_mult * topk), cand)
        b_f32 = index.planner.plan(
            "scan", (nq, nprobe * index.cap, d, topk)).hbm_bytes
        b_q8 = (iq8.planner.plan("scan_q8", (nq, cand, d, r),
                                 jnp.int8).hbm_bytes
                + iq8.planner.plan("scan",
                                   (nq, r, d, min(topk, r))).hbm_bytes)
        out.append(C.fmt_row(
            f"ivf_search_q8_nprobe{nprobe}_B{nq}", us,
            f"recall_at_{topk}={recall_at_k(ids, ids_ref):.3f};"
            f"modeled_scan_bytes_fp32={b_f32:.0f};"
            f"modeled_scan_bytes_q8={b_q8:.0f};"
            f"scan_bytes_reduction={b_f32 / b_q8:.2f}x"))

    # --- rescore tier: host reservoir round-trip vs device cache ----------
    # host_syncs_per_call counts device->host transfers on the q8 search
    # hot path (np.asarray + jax.device_get): the host reservoir pays one
    # id download + one row upload per call, the device cache pays zero.
    # dispatch/complete p50/p99 come from the overlapped SearchEngine
    # pipeline (pipeline_depth=2): overlapped_units counts units
    # dispatched while an earlier unit's arrays were still in flight.
    from repro import obs
    from repro.index import ivf as _ivf_mod
    from repro.serve.engine import SearchConfig, SearchEngine
    obs.enable()   # latency_stats reads the engine's spans

    def _count_syncs(idx):
        idx.search(q, topk=topk, nprobe=8)   # warm (compile excluded)
        n = {"c": 0}
        real_as, real_get = _ivf_mod.np.asarray, jax.device_get
        _ivf_mod.np.asarray = lambda *a, **kw: (
            n.__setitem__("c", n["c"] + 1), real_as(*a, **kw))[1]
        jax.device_get = lambda *a, **kw: (
            n.__setitem__("c", n["c"] + 1), real_get(*a, **kw))[1]
        try:
            idx.search(jnp.asarray(q), topk=topk, nprobe=8)
        finally:
            _ivf_mod.np.asarray, jax.device_get = real_as, real_get
        return n["c"]

    for kind in ("host", "device"):
        ir = IVFIndex.build(x, k=k, max_iters=8, codec="q8", rescore=kind)
        syncs = _count_syncs(ir)
        eng = SearchEngine(ir, SearchConfig(topk=topk, nprobe=8,
                                            query_batch=nq))
        eng.search(q)                        # warm the shape bucket
        reps = 8
        t0 = time.perf_counter()
        rids = [eng.submit(q) for _ in range(reps)]
        eng.pump()
        for rid in rids:
            eng.take(rid)
        us = (time.perf_counter() - t0) * 1e6 / reps
        lat = eng.latency_stats()
        out.append(C.fmt_row(
            f"ivf_search_q8_rescore_{kind}_B{nq}", us,
            f"host_syncs_per_call={syncs};"
            f"dispatch_p50_ms={lat['dispatch_p50_ms']:.3f};"
            f"dispatch_p99_ms={lat['dispatch_p99_ms']:.3f};"
            f"complete_p50_ms={lat['complete_p50_ms']:.3f};"
            f"complete_p99_ms={lat['complete_p99_ms']:.3f};"
            f"overlapped_units={lat['overlap_hits']}"))

    # --- two-level routed search at large K -------------------------------
    # K = 65536 cells (the regime the router exists for): one point per
    # cell, fine centroids clustered around 512 meta-centers — the
    # structured embedding-space geometry hierarchical routing assumes
    # (on unstructured uniform cells coarse coverage decays; see
    # DESIGN.md "Hierarchical routing"). The flat and routed indexes
    # share one bucket store, so any recall delta is pure routing.
    # modeled_* bytes are the planner's cost model per query tile
    # (route_group_cap = 2x mean group); live_gcap_* re-prices with the
    # trained member table's actual gcap (pow2 of the *max* group),
    # which is larger when the coarse groups are imbalanced.
    kb, db, nqb = 65536, 16, 64
    kmq = jax.random.split(jax.random.PRNGKey(4), 4)
    meta = jax.random.normal(kmq[0], (512, db)) * 8.0
    centb = meta[jax.random.randint(kmq[1], (kb,), 0, 512)] \
        + jax.random.normal(kmq[2], (kb, db))
    xb = centb + 0.05 * jax.random.normal(kmq[3], (kb, db))
    t0 = time.perf_counter()
    iflat = IVFIndex(centb, capacity=8)
    for lo in range(0, kb, 8192):
        iflat.add(xb[lo:lo + 8192])
    iflat.block_until_ready()
    us = (time.perf_counter() - t0) * 1e6
    out.append(C.fmt_row(
        f"ivf_ingest_K{kb}_d{db}", us,
        f"pts_per_s={kb / (us / 1e6):.0f};one_point_per_cell"))
    t0 = time.perf_counter()
    rt = TwoLevelRouter.train(centb, max_iters=4)
    us = (time.perf_counter() - t0) * 1e6
    out.append(C.fmt_row(
        f"ivf_router_train_K{kb}", us,
        f"coarse_k={rt.coarse_k};nprobe_c={rt.nprobe_c};gcap={rt.gcap}"))
    irouted = IVFIndex(centb, capacity=8, store=iflat.store, router=rt)
    qb = np.asarray(xb[:nqb])
    idsb_ref, _ = iflat.search_brute(qb, topk=topk)
    for nprobe in (10, 32):
        usf = C.wall_us(
            lambda qq, np_=nprobe: iflat.search(qq, topk=topk, nprobe=np_),
            qb, reps=3, warmup=1)
        idsf, _ = iflat.search(qb, topk=topk, nprobe=nprobe)
        bfb = heuristics.probe_bytes_flash(8, kb, db, nprobe)
        out.append(C.fmt_row(
            f"ivf_search_flat_K{kb}_nprobe{nprobe}_B{nqb}", usf,
            f"recall_at_{topk}={recall_at_k(idsf, idsb_ref):.3f};"
            f"modeled_probe_bytes_per_qtile={bfb:.0f}"))
        usr = C.wall_us(
            lambda qq, np_=nprobe: irouted.search(qq, topk=topk,
                                                  nprobe=np_),
            qb, reps=3, warmup=1)
        idsr, _ = irouted.search(qb, topk=topk, nprobe=nprobe)
        npc = rt.effective_nprobe_c(nprobe)
        g_model = heuristics.route_group_cap(kb, rt.coarse_k)
        brm = heuristics.probe_bytes_routed(8, kb, rt.coarse_k, npc,
                                            g_model, db, nprobe)
        brl = heuristics.probe_bytes_routed(8, kb, rt.coarse_k, npc,
                                            rt.gcap, db, nprobe)
        out.append(C.fmt_row(
            f"ivf_search_routed_K{kb}_nprobe{nprobe}_B{nqb}", usr,
            f"recall_at_{topk}={recall_at_k(idsr, idsb_ref):.3f};"
            f"nprobe_c={npc};"
            f"modeled_probe_bytes_per_qtile={brm:.0f};"
            f"modeled_probe_byte_reduction={bfb / brm:.2f}x;"
            f"live_gcap_probe_bytes={brl:.0f};"
            f"live_gcap_reduction={bfb / brl:.2f}x"))

    # --- sharded search: QPS + modeled collective bytes vs nprobe ---------
    from repro.core.parallel import (ParallelContext, make_host_mesh,
                                     search_collective_bytes_model)
    pctx = ParallelContext.for_mesh(make_host_mesh(1, len(jax.devices())))
    p_k = pctx.n_k_shards
    idx_sh = (IVFIndex.build(x, k=k, max_iters=8, pctx=pctx)
              if p_k > 1 and k % p_k == 0 else None)
    for nprobe in (2, 8, k):
        if idx_sh is not None:
            us = C.wall_us(
                lambda qq, np_=nprobe: idx_sh.search(qq, topk=topk,
                                                     nprobe=np_),
                q, reps=3, warmup=1)
            cb = idx_sh.search_collective_bytes(nq, topk, nprobe)
            label = f"ivf_search_sharded_p{p_k}_nprobe{nprobe}_B{nq}"
        else:
            # single-device host: report the wire model for a
            # hypothetical 8-way cells partition (wall = local search)
            us = C.wall_us(
                lambda qq, np_=nprobe: index.search(qq, topk=topk,
                                                    nprobe=np_),
                q, reps=3, warmup=1)
            cb = search_collective_bytes_model(nq, nprobe, topk, k, 8)
            label = f"ivf_search_sharded_model_p8_nprobe{nprobe}_B{nq}"
        out.append(C.fmt_row(
            label, us,
            f"collective_bytes_per_batch={cb};"
            f"bytes_per_query={cb / nq:.0f}"))

    # --- online add marginal cost vs refit --------------------------------
    r = 1024
    x_new = _blobs(jax.random.PRNGKey(2), r, k, d)
    t0 = time.perf_counter()
    index.add(x_new)
    index.refresh()
    jax.block_until_ready(index.centroids)
    us = (time.perf_counter() - t0) * 1e6
    iters = 8
    t_add = C.modeled_time_s(C.assign_flops(r, k, d),
                             C.assign_bytes_flash(r, k, d))
    t_refit = iters * C.modeled_time_s(
        C.lloyd_flops_fused(n + r, k, d),
        C.lloyd_bytes_fused(n + r, k, d))
    out.append(C.fmt_row(
        f"ivf_add_R{r}", us,
        f"modeled_add_us={t_add * 1e6:.1f};"
        f"modeled_refit_us={t_refit * 1e6:.1f};"
        f"speedup={t_refit / t_add:.0f}x"))

    # --- bucket memory under Zipf cell skew: padded vs paged --------------
    # identical results (id-identical, so identical recall) at a fraction
    # of the resident bytes: the padded layout pays K * hottest-cell
    # capacity while the paged pool pays occupied pages
    # (~n_total/page_size plus one partial page per non-empty cell)
    rng = np.random.default_rng(0)
    ranks = np.arange(1, k + 1, dtype=np.float64)
    pz = ranks ** -1.2
    cells_z = rng.choice(k, size=n, p=pz / pz.sum())
    kc, kn2 = jax.random.split(jax.random.PRNGKey(3))
    centers = jax.random.normal(kc, (k, d)) * 5.0
    xz = centers[cells_z] + 0.4 * jax.random.normal(kn2, (n, d))
    stores = {}
    for kind in ("padded", "paged"):
        t0 = time.perf_counter()
        iz = IVFIndex(centers, capacity=64, store=kind)
        for lo in range(0, n, 4096):
            iz.add(xz[lo:lo + 4096])
        iz.block_until_ready()
        stores[kind] = (iz, (time.perf_counter() - t0) * 1e6)
    pad_iz, pad_us = stores["padded"]
    pg_iz, pg_us = stores["paged"]
    ids_p, _ = pad_iz.search(q, topk=topk, nprobe=8)
    ids_g, _ = pg_iz.search(q, topk=topk, nprobe=8)
    cz = np.asarray(pad_iz.counts, np.float64)
    skew = cz.max() / max(1.0, cz.mean())
    st = pg_iz.store
    out.append(C.fmt_row(
        f"ivf_memory_zipf_N{n}_K{k}_d{d}", pad_us,
        f"store=padded;resident_bytes={pad_iz.resident_bytes()};"
        f"tail_cell_skew={skew:.1f};cap={pad_iz.cap}"))
    out.append(C.fmt_row(
        f"ivf_memory_zipf_N{n}_K{k}_d{d}", pg_us,
        f"store=paged;resident_bytes={pg_iz.resident_bytes()};"
        f"tail_cell_skew={skew:.1f};"
        f"occupied_pages={st.occupied_pages()};"
        f"page_size={st.page_size};"
        f"bytes_vs_padded={pg_iz.resident_bytes() / pad_iz.resident_bytes():.3f};"
        f"ids_identical={int(np.array_equal(np.asarray(ids_g), np.asarray(ids_p)))}"))

    # paged + q8: the two memory axes compose — page pool of int8 codes
    # (+ f32 scale sidecar) under the same Zipf skew; payload_bytes is
    # the apples-to-apples codes+ids(+scales) comparison against the
    # paged fp32 pool
    t0 = time.perf_counter()
    iq = IVFIndex(centers, capacity=64, store="paged", codec="q8")
    for lo in range(0, n, 4096):
        iq.add(xz[lo:lo + 4096])
    iq.block_until_ready()
    q8_us = (time.perf_counter() - t0) * 1e6
    ids_q, _ = iq.search(q, topk=topk, nprobe=8)
    out.append(C.fmt_row(
        f"ivf_memory_zipf_N{n}_K{k}_d{d}", q8_us,
        f"store=paged+q8;resident_bytes={iq.resident_bytes()};"
        f"payload_bytes={iq.store.payload_bytes()};"
        f"payload_vs_paged_fp32="
        f"{iq.store.payload_bytes() / pg_iz.resident_bytes():.3f};"
        f"recall_vs_padded_fp32={recall_at_k(ids_q, ids_p):.3f}"))
    return out


def main(argv=None) -> None:
    """``python -m benchmarks.bench_index [--json PATH]`` — prints the
    CSV rows; with ``--json`` also writes the parsed snapshot artifact
    (``BENCH_index.json``) that makes the perf trajectory diff-visible."""
    C.bench_main(rows, "index", argv)


if __name__ == "__main__":
    main()
