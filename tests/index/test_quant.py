"""Quantized bucket payloads + two-phase exact-rescore search.

Covers the codec contract (``index/quant.py``), the quantized store
wrapper (int8 pools + scale sidecars + rescore reservoir), the
``scan_q8`` kernel path, and the search-level guarantees: bitwise id
parity with brute force at full nprobe, recall vs ``rescore_mult`` on
clustered data, eviction honesty, snapshot v3 round-trips, and the
codec-aware bytes model the planner exposes.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))

from repro.core import plan as _plan
from repro.core.quant8 import SCALE_EPS
from repro.index import (IVFIndex, Fp32Codec, Int8ResidualCodec,
                         QuantizedBucketStore, RescoreReservoir,
                         default_codec_kind, make_codec, make_store,
                         make_quantized_store, recall_at_k)
from repro.index.store import restore_store
from repro.optim.compression import quantize_int8, dequantize_int8
from tests.conftest import assert_topk_match


def _blobs(key, n, k, d, spread=6.0, noise=0.3):
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (k, d)) * spread
    assign = jax.random.randint(ka, (n,), 0, k)
    x = centers[assign] + jax.random.normal(kn, (n, d)) * noise
    return x, centers


@pytest.fixture(scope="module")
def corpus():
    x, centers = _blobs(jax.random.PRNGKey(21), 2000, 16, 16)
    return x, centers


@pytest.fixture(params=["padded", "paged"])
def kind(request):
    return request.param


# --- codec contract --------------------------------------------------------

def test_q8_codec_roundtrip_error_bound():
    """Per-slot symmetric int8: reconstruction error is bounded by half
    a quantization step of each row's own residual absmax."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 32)) * 3.0
    c = jax.random.normal(jax.random.fold_in(key, 1), (64, 32))
    codec = Int8ResidualCodec()
    codes, scales = codec.encode(x, c)
    assert codes.dtype == jnp.int8 and scales.dtype == jnp.float32
    assert bool(jnp.all(scales >= SCALE_EPS))
    back = codec.decode(codes, scales, c)
    step = np.asarray(scales)[:, None]
    assert np.all(np.abs(np.asarray(back - x)) <= 0.5 * step + 1e-6)


def test_q8_codec_shares_compression_convention():
    """One symmetric-int8 convention repo-wide: encoding a residual via
    the codec equals ``optim.compression.quantize_int8`` on the same
    rows (block = row), code for code."""
    key = jax.random.PRNGKey(2)
    d = 256                               # == compression's BLOCK
    x = jax.random.normal(key, (8, d)) * 2.0
    codec = Int8ResidualCodec()
    codes, scales = codec.encode(x, jnp.zeros((8, d)))
    qc, qs = quantize_int8(x.reshape(-1))
    np.testing.assert_array_equal(np.asarray(codes).reshape(-1),
                                  np.asarray(qc).reshape(-1))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(qs))
    np.testing.assert_allclose(
        np.asarray(codec.decode(codes, scales, jnp.zeros((8, d)))),
        np.asarray(dequantize_int8(qc, qs, (8, d))))


def test_fp32_codec_is_identity():
    codec = Fp32Codec()
    x = jnp.arange(12.0).reshape(3, 4)
    codes, scales = codec.encode(x, jnp.zeros((3, 4)))
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(x))
    assert bool(jnp.all(scales == 1.0))
    assert codec.score_bytes(4) == 16


def test_codec_bytes_model():
    """The modeled per-row scan bytes: q8 pays d + 4 against fp32's 4d
    — >= 2x smaller for every d >= 2 (the acceptance floor), ~3.6x at
    d = 32, asymptotically 4x."""
    q8, fp = Int8ResidualCodec(), Fp32Codec()
    for d in (8, 32, 128):
        assert fp.score_bytes(d) / q8.score_bytes(d) >= 2.0
    assert fp.score_bytes(32) / q8.score_bytes(32) > 3.5


def test_default_codec_kind_env(monkeypatch):
    monkeypatch.delenv("REPRO_BUCKET_CODEC", raising=False)
    assert default_codec_kind() == "fp32"
    monkeypatch.setenv("REPRO_BUCKET_CODEC", "q8")
    assert default_codec_kind() == "q8"
    idx = IVFIndex(jnp.zeros((4, 8)), capacity=16)
    assert idx.codec_kind == "q8"
    monkeypatch.setenv("REPRO_BUCKET_CODEC", "fp8")
    with pytest.raises(ValueError, match="REPRO_BUCKET_CODEC"):
        default_codec_kind()
    with pytest.raises(ValueError, match="unknown codec"):
        make_codec("fp8")


# --- store wrapper ---------------------------------------------------------

def test_quantized_store_dense_is_exact_with_reservoir(kind):
    """With the (default) reservoir, ``dense()`` overlays the original
    fp32 rows — the oracle view is exact, so brute force and two-phase
    rescore score identical rows."""
    rng = np.random.default_rng(3)
    k, d, n = 8, 16, 300
    anchors = rng.normal(size=(k, d)).astype(np.float32)
    st = make_quantized_store(kind, k, d, jnp.float32, anchors=anchors,
                              capacity=8, page_size=8)
    cells = np.sort(rng.integers(0, k, size=n).astype(np.int32))
    rows = rng.normal(size=(n, d)).astype(np.float32)
    st.append(cells, jnp.asarray(rows), np.arange(n, dtype=np.int32))
    assert st.kind == kind and st.codec_kind == "q8"
    x, ids = st.dense()
    for c in range(k):
        for s in range(x.shape[1]):
            if ids[c, s] >= 0:
                np.testing.assert_array_equal(x[c, s], rows[ids[c, s]])
    # payload pool is int8: ~4x smaller than the fp32 equivalent
    fp = make_store(kind, k, d, jnp.float32, capacity=8, page_size=8)
    fp.append(cells, jnp.asarray(rows), np.arange(n, dtype=np.int32))
    assert st.payload_bytes() < 0.45 * fp.resident_bytes()


def test_quantized_store_dense_decodes_without_reservoir(kind):
    rng = np.random.default_rng(4)
    k, d, n = 4, 8, 100
    anchors = rng.normal(size=(k, d)).astype(np.float32)
    st = make_quantized_store(kind, k, d, jnp.float32, anchors=anchors,
                              capacity=8, page_size=8, reservoir=False)
    cells = np.sort(rng.integers(0, k, size=n).astype(np.int32))
    rows = rng.normal(size=(n, d)).astype(np.float32)
    st.append(cells, jnp.asarray(rows), np.arange(n, dtype=np.int32))
    x, ids = st.dense()
    errs = [np.max(np.abs(x[c, s] - rows[ids[c, s]]))
            for c in range(k) for s in range(x.shape[1]) if ids[c, s] >= 0]
    assert 0.0 < max(errs) < 0.2          # lossy but close


def test_gather_width_floors_at_sublane(kind):
    """Regression: the pow2 gather-width bucket must never drop below
    the planner's sublane minimum — 8 slots for fp32, 32 for int8 pools
    (the (32, 128) minimum int8 tile) — even when cells hold 1-2 rows."""
    st = make_store(kind, 4, 8, jnp.float32, capacity=8, page_size=8)
    st.append(np.array([0, 1], np.int32), jnp.ones((2, 8)),
              np.arange(2, dtype=np.int32))
    assert st.gather_width(1) >= 8
    q8 = make_quantized_store(kind, 4, 8, jnp.float32,
                              anchors=np.zeros((4, 8), np.float32),
                              capacity=64, page_size=8)
    q8.append(np.array([0, 1], np.int32), jnp.ones((2, 8)),
              np.arange(2, dtype=np.int32))
    assert q8.gather_width(1) >= 32          # int8 min tile is (32, 128)


def test_rescore_reservoir_fifo_budget():
    d = 8
    cap_rows = 10
    res = RescoreReservoir(d, max_bytes=cap_rows * (4 * d + 8))
    ids = np.arange(25, dtype=np.int64)
    rows = np.arange(25 * d, dtype=np.float32).reshape(25, d)
    res.put(ids[:15], rows[:15])
    res.put(ids[15:], rows[15:])
    assert len(res) == cap_rows and res.evicted == 15
    got, found = res.lookup(ids)
    assert not found[:15].any() and found[15:].all()   # FIFO: oldest gone
    np.testing.assert_array_equal(got[15:], rows[15:])
    assert not res.lookup(np.array([-1, 999]))[1].any()
    # overwrite of a resident id updates in place, no eviction
    res.put(ids[20:21], rows[20:21] + 1.0)
    assert res.evicted == 15
    np.testing.assert_array_equal(res.lookup(ids[20:21])[0][0],
                                  rows[20] + 1.0)


# --- two-phase search ------------------------------------------------------

def test_full_nprobe_reproduces_brute_force_exact(kind):
    """The tentpole guarantee: quantized propose + exact rescore at
    full nprobe (R covering topk) returns brute force's ids exactly —
    asserted bitwise on a tie-free corpus (same convention as the fp32
    ``test_full_probe_equals_brute_tiny``)."""
    rng = np.random.default_rng(9)
    n, d, k = 900, 24, 16
    x = rng.normal(size=(n, d)).astype(np.float32)
    idx = IVFIndex.build(x, k=k, max_iters=5, store=kind, codec="q8",
                         page_size=8)
    assert idx.codec_kind == "q8"
    q = x[:48]
    ids_bf, d_bf = idx.search_brute(q, topk=10)
    ids, dists = idx.search(q, topk=10, nprobe=k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_bf))
    np.testing.assert_allclose(np.asarray(dists), np.asarray(d_bf),
                               rtol=1e-5, atol=1e-4)
    # online mutation keeps it (appends encode against frozen anchors)
    idx.add(rng.normal(size=(100, d)).astype(np.float32))
    idx.refresh()
    ids_bf, _ = idx.search_brute(q, topk=10)
    ids, _ = idx.search(q, topk=10, nprobe=k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_bf))


def test_full_nprobe_matches_brute_on_clusters(corpus, kind):
    """Clustered corpus (near-duplicate distances exist): full-nprobe
    two-phase search matches brute force up to near-tie swaps — the
    identical contract the fp32 path satisfies on this data."""
    x, centers = corpus
    idx = IVFIndex(jnp.asarray(centers), capacity=128, store=kind,
                   codec="q8", page_size=16)
    idx.add(x)
    q = x[:64]
    ids_bf, d_bf = idx.search_brute(q, topk=10)
    ids, d = idx.search(q, topk=10, nprobe=16)
    assert_topk_match(ids, d, ids_bf, d_bf)


def test_recall_vs_rescore_mult(corpus, kind):
    """Clustered corpus, partial nprobe: the quantized+rescore path at
    rescore_mult >= 4 retrieves at least the fp32 path's recall@10 (the
    proposal pool is wide enough that quantization error in phase 1
    cannot cost a true neighbour), and recall grows with the mult."""
    x, centers = corpus
    q = x[500:564]
    fp = IVFIndex(jnp.asarray(centers), capacity=128, store=kind,
                  codec="fp32", page_size=16)
    fp.add(x)
    ids_bf, _ = fp.search_brute(q, topk=10)
    ids_fp, _ = fp.search(q, topk=10, nprobe=4)
    r_fp = recall_at_k(ids_fp, ids_bf)
    recalls = {}
    for mult in (1, 4, 8):
        qz = IVFIndex(jnp.asarray(centers), capacity=128, store=kind,
                      codec="q8", page_size=16, rescore_mult=mult)
        qz.add(x)
        ids_q, _ = qz.search(q, topk=10, nprobe=4)
        recalls[mult] = recall_at_k(ids_q, ids_bf)
    assert recalls[4] >= r_fp
    assert recalls[8] >= r_fp
    assert recalls[8] >= recalls[4] >= recalls[1]


def test_q8_search_plans_cache_zero_chooser_calls(corpus, kind):
    """Repeated two-phase traffic at a fixed geometry replans nothing:
    probe, scan_q8 and rescore plans are all cached on the index."""
    x, centers = corpus
    planner = _plan.KernelPlanner()
    idx = IVFIndex(jnp.asarray(centers), capacity=128, store=kind,
                   codec="q8", page_size=16, planner=planner)
    idx.add(x)
    q = x[:32]
    idx.search(q, topk=10, nprobe=4)
    calls = planner.chooser_calls
    for _ in range(3):
        idx.search(q, topk=10, nprobe=4)
    assert planner.chooser_calls == calls


def test_q8_paged_eviction_stays_honest():
    """Byte-budgeted q8 paged store: the LRU evictor frees int8 pages
    (and their scale strips), the reservoir drops evicted ids from its
    overlay view, and full-nprobe search still matches brute force over
    what *remains* stored."""
    d, ps = 8, 8
    centers = jnp.asarray(np.eye(4, d, dtype=np.float32) * 40.0)
    budget = 8 * ps * (d * 1 + 4 + 4)    # 8 q8 pages (+4: scale strip)
    idx = IVFIndex(centers, capacity=16, store="paged", page_size=ps,
                   store_bytes=budget, codec="q8")
    key = jax.random.PRNGKey(7)
    for c in range(4):
        idx.add(centers[c] + 0.1 * jax.random.normal(
            jax.random.fold_in(key, c), (3 * ps, d)))
    assert idx.evicted > 0
    assert int(idx.evict_counts[3]) == 0  # hottest cell survives
    ids, dists = idx.search(centers + 0.05, topk=4, nprobe=4)
    valid = np.asarray(ids) >= 0
    assert bool(np.all(np.isfinite(np.asarray(dists)[valid])))
    ids_bf, _ = idx.search_brute(centers + 0.05, topk=4)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_bf))


def test_reservoir_byte_budget_falls_back_to_decode(corpus, kind):
    """A tight rescore budget evicts old originals from the reservoir;
    rescore falls back to decoded codes for those ids — recall degrades
    gracefully, never an error, and stays near the unbounded path."""
    x, centers = corpus
    idx = IVFIndex(jnp.asarray(centers), capacity=128, store=kind,
                   codec="q8", page_size=16,
                   rescore_bytes=200 * (4 * 16 + 8))   # ~200 of 2000 rows
    idx.add(x)
    assert idx.store.reservoir.evicted > 0
    q = x[:64]
    ids_bf, _ = idx.search_brute(q, topk=10)
    ids, _ = idx.search(q, topk=10, nprobe=16)
    assert recall_at_k(ids, ids_bf) > 0.95


# --- durability ------------------------------------------------------------

def test_snapshot_v3_roundtrip(corpus, kind, tmp_path):
    x, centers = corpus
    idx = IVFIndex(jnp.asarray(centers), capacity=128, store=kind,
                   codec="q8", page_size=16)
    idx.add(x)
    q = x[:32]
    ids0, d0 = idx.search(q, topk=10, nprobe=16)
    idx.save(str(tmp_path), seqno=5)
    from repro.reliability.snapshot import read_manifest, SNAPSHOT_VERSION
    man = read_manifest(str(tmp_path))
    assert man["version"] == SNAPSHOT_VERSION >= 3
    assert man["store"]["codec"] == "q8" and man["store"]["reservoir"]
    back = IVFIndex.load(str(tmp_path))
    assert isinstance(back.store, QuantizedBucketStore)
    assert back.store.kind == kind and back.codec_kind == "q8"
    ids1, d1 = back.search(q, topk=10, nprobe=16)
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids0))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
    # restored index keeps mutating with the same contract
    back.add(x[:100] + 0.02)
    ids_bf, _ = back.search_brute(q, topk=10)
    ids2, _ = back.search(q, topk=10, nprobe=16)
    np.testing.assert_array_equal(np.asarray(ids2), np.asarray(ids_bf))


def test_v2_manifest_without_codec_restores_fp32(kind):
    """Snapshot back-compat: a manifest whose store meta predates the
    codec axis (v1/v2 — no "codec" key) restores as a plain fp32 store."""
    st = make_store(kind, 4, 8, jnp.float32, capacity=8, page_size=8)
    st.append(np.array([0, 0, 1], np.int32), jnp.ones((3, 8)),
              np.arange(3, dtype=np.int32))
    host = {k: np.asarray(v) for k, v in st.state_arrays().items()}
    meta = {k: v for k, v in st.meta().items() if k != "codec"}
    assert "codec" not in meta
    back = restore_store(host, meta, k=4, d=8, dtype=jnp.float32)
    assert not isinstance(back, QuantizedBucketStore)
    assert back.codec_kind == "fp32"
    np.testing.assert_array_equal(back.dense()[1], st.dense()[1])


# --- planner ---------------------------------------------------------------

def test_planner_scan_q8_bytes_model():
    """The scan_q8 plan's modeled HBM traffic reflects the codec: >= 2x
    below the fp32 scan at the same geometry (the acceptance floor)."""
    planner = _plan.KernelPlanner()
    b, c, d, l = 64, 256, 32, 40
    p_fp = planner.plan("scan", (b, c, d, l), jnp.float32)
    p_q8 = planner.plan("scan_q8", (b, c, d, l), jnp.int8)
    assert p_q8.impl == "grouped_scan_q8"
    assert p_fp.hbm_bytes / p_q8.hbm_bytes >= 2.0
    assert p_q8.vmem_bytes > 0 and p_q8.blocks is not None
