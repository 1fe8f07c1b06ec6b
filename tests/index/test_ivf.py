"""FlashIVF acceptance tests: full-probe exactness vs brute force,
recall at partial probing, online add/refresh behaviour, CSR posting-list
structure, the fused top-L kernel vs jax.lax.top_k, and the search
serving engine (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.streaming import SufficientStats
from repro.index import IVFIndex
from repro.index.ivf import csr_from_assignments
from repro.kernels import ops, ref
from tests.conftest import assert_topk_match, f32_score_tol


def _blobs(key, n, k, d, spread=6.0, noise=0.3):
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (k, d)) * spread
    assign = jax.random.randint(ka, (n,), 0, k)
    x = centers[assign] + jax.random.normal(kn, (n, d)) * noise
    return x, centers


@pytest.fixture(scope="module")
def built():
    x, centers = _blobs(jax.random.PRNGKey(0), 2000, 16, 16)
    index = IVFIndex.build(x, k=16, max_iters=8)
    return x, centers, index


# --- acceptance (a): nprobe = k equals brute force -------------------------

def test_full_probe_equals_brute(built):
    x, _, index = built
    q = x[:48]
    ids, dists = index.search(q, topk=10, nprobe=16)
    ids_ref, dists_ref = index.search_brute(q, topk=10)
    assert_topk_match(ids, dists, ids_ref, dists_ref)
    # self-queries come back at rank 0 with distance ~0
    assert np.array_equal(np.asarray(ids[:, 0]), np.arange(48))


def test_full_probe_equals_brute_tiny():
    """Tiny, well-separated shape, every cell probed: the result is the
    brute-force top-k. The two paths evaluate the expanded distance
    ``||q||^2 + ||x||^2 - 2 q.x`` in different XLA graphs, so distances
    agree within a stated f32 tolerance (8 ulps of the largest term) and
    ids may differ only by swaps of near-ties inside it."""
    x, _ = _blobs(jax.random.PRNGKey(3), 200, 4, 8)
    index = IVFIndex.build(x, k=4, max_iters=6)
    q = x[:16]
    ids, dists = index.search(q, topk=5, nprobe=4)
    ids_ref, dists_ref = index.search_brute(q, topk=5)
    assert_topk_match(ids, dists, ids_ref, dists_ref,
                      tol=f32_score_tol(q, x), rtol=0)


# --- acceptance (b): recall@10 at nprobe = k/4 -----------------------------

def test_recall_at_partial_probe(built):
    x, _, index = built
    q = x[100:164]
    ids, _ = index.search(q, topk=10, nprobe=4)          # k/4
    ids_ref, _ = index.search_brute(q, topk=10)
    recall = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / 10
        for a, b in zip(np.asarray(ids), np.asarray(ids_ref))])
    assert recall >= 0.9, f"recall@10 = {recall}"


@pytest.mark.parametrize("store", ["padded", "paged"])
def test_partial_probe_matches_numpy_oracle(store):
    """At partial nprobe the list-major scan returns what a NumPy oracle
    does: the exact top-nprobe probe over the centroids, then an exact
    scan of the probed lists' rows (the store's host view, in (rank,
    slot) order), per store kind."""
    x, _ = _blobs(jax.random.PRNGKey(11), 1500, 24, 16)
    kw = {"page_size": 16} if store == "paged" else {}
    index = IVFIndex.build(x, k=24, max_iters=6, store=store, **kw)
    q = np.asarray(x[::50]) + 0.05
    nprobe, topk = 5, 10
    ids, dists = index.search(jnp.asarray(q), topk=topk, nprobe=nprobe)
    cents = np.asarray(index.centroids, np.float64)
    rows, row_ids = index.store.dense()
    counts = np.asarray(index.counts)
    ids_ref = np.zeros((len(q), topk), np.int64)
    dists_ref = np.zeros((len(q), topk))
    for i, qi in enumerate(q.astype(np.float64)):
        probe = np.argsort(np.sum((cents - qi) ** 2, axis=1),
                           kind="stable")[:nprobe]
        cand = [(j, s) for j in probe for s in range(counts[j])]
        dist = np.array([np.sum((rows[j, s].astype(np.float64) - qi) ** 2)
                         for j, s in cand])
        order = np.argsort(dist, kind="stable")[:topk]
        ids_ref[i] = [row_ids[cand[o]] for o in order]
        dists_ref[i] = dist[order]
    assert_topk_match(ids, dists, ids_ref, dists_ref,
                      tol=f32_score_tol(q, x), rtol=0)


# --- acceptance (c): online add + refresh ----------------------------------

def test_add_refresh_finds_new_vectors(built):
    x, centers, _ = built
    index = IVFIndex.build(x, k=16, max_iters=8)         # fresh copy
    n0 = len(index)
    x_new = centers[:8] + 0.05
    a = index.add(x_new)
    assert a.shape == (8,) and len(index) == n0 + 8
    index.refresh()
    ids, dists = index.search(x_new, topk=5, nprobe=4)
    assert np.array_equal(np.asarray(ids[:, 0]),
                          n0 + np.arange(8))             # rank 0 = themselves
    np.testing.assert_allclose(np.asarray(dists[:, 0]), 0.0, atol=1e-3)


def test_refresh_recommits_stats(built):
    x, _, _ = built
    index = IVFIndex.build(x, k=16, max_iters=8)
    c_before = np.asarray(index.centroids)
    w_before = float(index.stats.weight)
    assert w_before == pytest.approx(len(index))
    # heavy drift batch far from everything pulls its cell's centroid
    x_new = jnp.full((64, 16), 25.0)
    cell = int(index.add(x_new)[0])
    index.refresh()
    c_after = np.asarray(index.centroids)
    assert float(index.stats.weight) == pytest.approx(len(index))
    assert np.abs(c_after[cell] - c_before[cell]).max() > 1.0
    # refresh with no pending evidence is a no-op on the centroids
    c2 = np.asarray(index.refresh().centroids)
    np.testing.assert_allclose(c2, c_after)


def test_add_empty_batch_is_noop():
    x, _ = _blobs(jax.random.PRNGKey(9), 100, 4, 8)
    index = IVFIndex.build(x, k=4, max_iters=2)
    a = index.add(jnp.zeros((0, 8)))
    assert a.shape == (0,) and len(index) == 100
    c2 = np.asarray(index.refresh().centroids)
    assert np.all(np.isfinite(c2))


def test_capacity_grows_on_skewed_adds():
    x, _ = _blobs(jax.random.PRNGKey(5), 300, 4, 8)
    index = IVFIndex.build(x, k=4, max_iters=4)
    cap0 = index.cap
    hot = jnp.tile(x[:1], (cap0 + 40, 1))                # one hot cell
    index.add(hot + 0.01 * jax.random.normal(
        jax.random.PRNGKey(6), hot.shape))
    assert index.cap > cap0
    ids, offsets = index.posting_lists()
    assert int(offsets[-1]) == len(index)
    assert np.array_equal(np.sort(np.asarray(ids)), np.arange(len(index)))


# --- acceptance (d): fused top-L == jax.lax.top_k --------------------------

def test_flash_probe_bit_exact_vs_topk():
    """Single-K-tile tiny shapes, kernel-level scores vs ``top_k`` of the
    dense score matrix: the interpret-mode tile dot and the oracle's
    dense dot may round the d-reduction differently, so scores agree
    within a stated f32 tolerance (8 ulps of the largest term) and
    indices may differ only by swaps of near-ties inside it."""
    for (n, k, d, l) in [(16, 8, 8, 4), (32, 16, 8, 8), (24, 16, 4, 4)]:
        kq, kc = jax.random.split(jax.random.PRNGKey(n + k))
        q = jax.random.normal(kq, (n, d))
        c = jax.random.normal(kc, (k, d))
        idx, v = ops.flash_probe(q, c, l=l, block_n=n, block_k=k,
                                 want_dists=False)
        idx_ref, v_ref = ref.probe_ref(q, c, l, want_dists=False)
        assert_topk_match(idx, v, idx_ref, v_ref, tol=f32_score_tol(q, c),
                          rtol=0)


# --- CSR construction ------------------------------------------------------

def test_csr_from_assignments_is_inverse_mapping():
    a = jnp.asarray([2, 0, 2, 1, 0, 2, 4], jnp.int32)
    order, offsets = csr_from_assignments(a, 5)
    assert np.array_equal(np.asarray(offsets), [0, 2, 3, 6, 6, 7])
    # stable: original order preserved within each cluster
    assert np.array_equal(np.asarray(order), [1, 4, 3, 0, 2, 5, 6])
    # empty cluster 3 is a zero-length segment


def test_build_posting_lists_partition_corpus(built):
    x, _, index = built
    ids, offsets = index.posting_lists()
    assert int(offsets[-1]) == 2000
    assert np.array_equal(np.sort(np.asarray(ids)), np.arange(2000))
    # every stored row matches its source vector
    a, _ = ops.flash_assign(x, index.centroids)
    counts = np.bincount(np.asarray(a), minlength=16)
    assert np.array_equal(np.asarray(index.counts), counts)


def test_search_validates_topk():
    x, _ = _blobs(jax.random.PRNGKey(7), 100, 4, 8)
    index = IVFIndex.build(x, k=4, max_iters=2)
    with pytest.raises(ValueError, match="candidate pool"):
        index.search(x[:4], topk=10_000, nprobe=1)


# --- out-of-core build -----------------------------------------------------

def test_chunked_build_matches_incore_contract():
    x, _ = _blobs(jax.random.PRNGKey(8), 1200, 8, 12)
    index = IVFIndex.build(np.asarray(x), k=8, max_iters=4, chunk_size=400)
    assert len(index) == 1200
    ids, offsets = index.posting_lists()
    assert np.array_equal(np.sort(np.asarray(ids)), np.arange(1200))
    q = x[:24]
    ids_f, d_f = index.search(q, topk=8, nprobe=8)
    ids_b, d_b = index.search_brute(q, topk=8)
    assert_topk_match(ids_f, d_f, ids_b, d_b)


# --- serving engine --------------------------------------------------------

def test_search_engine_pads_and_refreshes(built):
    from repro.serve.engine import SearchConfig, SearchEngine
    x, centers, _ = built
    index = IVFIndex.build(x, k=16, max_iters=6)
    eng = SearchEngine(index, SearchConfig(topk=5, nprobe=4,
                                           query_batch=64,
                                           refresh_every=2))
    ids, dists = eng.search(x[:10])                      # padded to 64
    assert ids.shape == (10, 5) and dists.shape == (10, 5)
    assert np.array_equal(np.asarray(ids[:, 0]), np.arange(10))
    assert eng.queries_served == 10
    eng.add(centers[:4] + 0.02)
    assert eng.refresh_count == 0
    eng.add(centers[4:8] + 0.02)                         # 2nd add -> flush
    assert eng.refresh_count == 1 and eng.adds_since_refresh == 0
    # oversized batches split into padded sub-batches, same executable
    ids, dists = eng.search(x[:65])
    assert ids.shape == (65, 5) and dists.shape == (65, 5)
    assert np.array_equal(np.asarray(ids[:, 0]), np.arange(65))
    one, _ = eng.search(x[64:65])
    assert np.array_equal(np.asarray(ids[64]), np.asarray(one[0]))


# --- planner integration: no chooser on the hot path -----------------------

def test_search_zero_chooser_calls_after_first_query(built):
    """Regression guard for the per-call chooser recompute on the search
    hot path: for a repeated geometry every dispatch after the first is a
    pure KernelPlanner cache hit (counter hook on the planner)."""
    from repro.core import heuristics as H
    from repro.core.plan import KernelPlanner
    x, _, _ = built
    planner = KernelPlanner(hw=H.TPU_V5E, persist=False)
    index = IVFIndex.build(x, k=16, max_iters=4, planner=planner)
    q = x[:48]
    index.search(q, topk=5, nprobe=4)                   # first: plans
    frozen = planner.chooser_calls
    for _ in range(4):
        index.search(q, topk=5, nprobe=4)
    assert planner.chooser_calls == frozen
    assert len(index._search_plans) == 1                # cached on the index
    # a genuinely new geometry may plan again...
    index.search(q, topk=5, nprobe=8)
    grew = planner.chooser_calls
    index.search(q, topk=5, nprobe=8)
    assert planner.chooser_calls == grew                # ...exactly once
    # repeated same-size adds replan nothing either
    index.add(x[:100])
    after_add = planner.chooser_calls
    index.add(x[100:200])
    assert planner.chooser_calls == after_add


def test_search_engine_zero_chooser_calls(built):
    """SearchEngine pins its (padded) batch geometry at config time: the
    whole serve loop — search and insert traffic — runs chooser-free."""
    from repro.core import heuristics as H
    from repro.core.plan import KernelPlanner
    from repro.serve.engine import SearchConfig, SearchEngine
    x, _, _ = built
    planner = KernelPlanner(hw=H.TPU_V5E, persist=False)
    index = IVFIndex.build(x, k=16, max_iters=4, planner=planner)
    eng = SearchEngine(index, SearchConfig(topk=5, nprobe=4,
                                           query_batch=64,
                                           refresh_every=1000))
    assert eng.pinned_plan is not None                  # pinned at config
    eng.add(x[:64])          # first insert: plans its batch bucket, and
    eng.search(x[:10])       # may grow cap (re-keying the scan geometry)
    frozen = planner.chooser_calls
    for lo in range(0, 60, 20):                         # ragged real batches
        eng.search(x[lo:lo + 17])
    eng.add(x[64:128])       # same-bucket insert: replans nothing
    eng.search(x[:32])
    assert planner.chooser_calls == frozen


# --- reliability: durability + capacity budget -----------------------------

def test_snapshot_roundtrip_bitwise(built, tmp_path):
    """save -> load restores the full index state: identical searches,
    identical pending stats, restored plan cache."""
    x, _, _ = built
    index = IVFIndex.build(x, k=16, max_iters=6)
    index.add(x[:100])                       # leave pending evidence
    q = x[:32]
    ids0, d0 = index.search(q, topk=5, nprobe=4)
    index.save(str(tmp_path), seqno=7, extra={"note": 1})
    back = IVFIndex.load(str(tmp_path))
    ids1, d1 = back.search(q, topk=5, nprobe=4)
    assert np.array_equal(np.asarray(ids0), np.asarray(ids1))
    assert np.array_equal(np.asarray(d0), np.asarray(d1))
    assert back.n_total == index.n_total
    assert back._search_plans == index._search_plans
    np.testing.assert_array_equal(np.asarray(back._pending.counts),
                                  np.asarray(index._pending.counts))
    # refresh after restore == refresh before: same committed centroids
    index.refresh()
    back.refresh()
    np.testing.assert_array_equal(np.asarray(index.centroids),
                                  np.asarray(back.centroids))


def test_snapshot_manifest_validation(built, tmp_path):
    """A corrupted snapshot fails with a named mismatch, not a tree error."""
    from repro.reliability.snapshot import read_manifest
    x, _, _ = built
    index = IVFIndex.build(x, k=16, max_iters=4)
    index.save(str(tmp_path), seqno=1)
    man = read_manifest(str(tmp_path))
    assert man["seqno"] == 1 and "centroids" in man["arrays"]
    # truncate the npz payload of one key
    import numpy as _np
    path = tmp_path / "index_00000001.npz"
    with _np.load(path) as data:
        host = {k: data[k] for k in data.files}
    host["counts"] = host["counts"][:-1]
    _np.savez(path, **host)
    with pytest.raises(ValueError, match="counts"):
        IVFIndex.load(str(tmp_path))


def test_capacity_budget_spills_instead_of_growing(built):
    """max_cap bounds bucket memory: overflow rows are counted (per cell)
    but never stored, ids stay monotone, search stays finite."""
    x, _, _ = built
    index = IVFIndex.build(x, k=16, max_iters=4, max_cap=64)
    for lo in range(0, 2000, 250):
        index.add(x[lo:lo + 250])
    assert index.cap <= 64
    assert index.spilled > 0
    assert int(index.spill_counts.sum()) == index.spilled
    ids, offsets = index.posting_lists()
    assert int(offsets[-1]) == index.n_total - index.spilled
    assert int(jnp.max(index.counts)) <= index.cap
    q = x[:16]
    sids, sdists = index.search(q, topk=5, nprobe=4)
    assert bool(jnp.all(jnp.isfinite(sdists)))
    # snapshots carry the spill accounting through a restore
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        index.save(td)
        back = IVFIndex.load(td)
        assert back.spilled == index.spilled and back.cap == index.cap
        assert back.max_cap == index.max_cap
        np.testing.assert_array_equal(back.spill_counts,
                                      index.spill_counts)
