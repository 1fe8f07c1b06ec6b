"""FlashProbe fused top-L kernel vs the jax.lax.top_k dense oracle:
tie-aware parity within a stated f32 tolerance on single-K-tile shapes,
index-exactness + tight value
agreement across tiled/ragged shapes, tie-breaking parity, the grouped
(per-query-candidate) scan variant, and argmin (L=1) equivalence with
FlashAssign (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import heuristics
from repro.kernels import ops, ref
from tests.conftest import assert_topk_match, f32_score_tol


def _data(n, k, d, dtype=jnp.float32, seed=0):
    kq, kc = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (n, d), dtype)
    c = jax.random.normal(kc, (k, d), dtype)
    return q, c


# one K tile, no shape padding, short d-reduction: the kernel's tile dot
# (interpret mode) and the oracle's dense dot are different XLA graphs
# that may round the d-reduction differently, so kernel-level parity is
# tie-aware within a stated f32 tolerance (8 ulps of the largest term)
TINY = [(16, 8, 8, 4), (32, 16, 8, 4), (64, 32, 8, 8), (8, 8, 8, 8),
        (24, 16, 4, 4)]


@pytest.mark.parametrize("n,k,d,l", TINY)
def test_bit_exact_vs_topk_tiny(n, k, d, l):
    q, c = _data(n, k, d, seed=n + k)
    # kernel-level scores vs top_k of the dense matrix
    idx, v = ops.flash_probe(q, c, l=l, block_n=max(n, 8), block_k=max(k, 8),
                             want_dists=False)
    idx_ref, v_ref = ref.probe_ref(q, c, l, want_dists=False)
    assert_topk_match(idx, v, idx_ref, v_ref, tol=f32_score_tol(q, c), rtol=0)
    # true distances: the ||q||^2 re-add lives in two different XLA
    # graphs, so parity is ULP-tight rather than bitwise
    _, dv = ops.flash_probe(q, c, l=l, block_n=max(n, 8), block_k=max(k, 8))
    _, dv_ref = ref.probe_ref(q, c, l)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref),
                               rtol=1e-6, atol=1e-5)


# ragged N/K (padding + multi-tile K sweep): the tiled dot may round
# differently at ULP level, so indices must match but values are close
RAGGED = [(100, 37, 19, 5), (257, 129, 33, 10), (513, 100, 7, 16),
          (33, 65, 3, 65), (1000, 256, 64, 32)]


@pytest.mark.parametrize("n,k,d,l", RAGGED)
def test_topk_parity_ragged(n, k, d, l):
    q, c = _data(n, k, d, seed=n)
    idx, v = ops.flash_probe(q, c, l=l, block_n=64, block_k=32)
    idx_ref, v_ref = ref.probe_ref(q, c, l)
    assert np.array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                               rtol=1e-5, atol=1e-5)


def test_exact_ties_break_to_lower_index():
    """Duplicated centroids: top_k prefers the lower index; so must we."""
    q, c = _data(50, 12, 6, seed=3)
    c = jnp.concatenate([c, c, c[:4]])          # many exact duplicates
    idx, v = ops.flash_probe(q, c, l=12, block_n=16, block_k=8)
    idx_ref, v_ref = ref.probe_ref(q, c, 12)
    assert np.array_equal(np.asarray(idx), np.asarray(idx_ref))


def test_l_equals_1_matches_flash_assign():
    q, c = _data(200, 40, 12, seed=1)
    idx, v = ops.flash_probe(q, c, l=1)
    a, m = ops.flash_assign(q, c)
    assert np.array_equal(np.asarray(idx[:, 0]), np.asarray(a))
    np.testing.assert_allclose(np.asarray(v[:, 0]), np.asarray(m),
                               rtol=1e-6)


def test_block_shape_invariance():
    q, c = _data(130, 70, 9, seed=7)
    outs = [ops.flash_probe(q, c, l=7, block_n=bn, block_k=bk)
            for bn, bk in [(8, 8), (128, 128), (64, 16)]]
    i0, v0 = outs[0]
    for i1, v1 in outs[1:]:
        assert np.array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(v0), np.asarray(v1),
                                   rtol=1e-5, atol=1e-5)


def test_values_sorted_ascending():
    q, c = _data(64, 50, 5, seed=9)
    _, v = ops.flash_probe(q, c, l=10)
    v = np.asarray(v)
    assert np.all(np.diff(v, axis=1) >= 0)


def test_want_dists_false_omits_query_norm():
    q, c = _data(20, 10, 4, seed=2)
    _, v = ops.flash_probe(q, c, l=3, want_dists=False)
    _, vd = ops.flash_probe(q, c, l=3, want_dists=True)
    qsq = np.sum(np.asarray(q, np.float32) ** 2, axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(vd),
                               np.maximum(np.asarray(v) + qsq, 0.0),
                               rtol=1e-6, atol=1e-6)


def test_l_bounds_raise():
    q, c = _data(10, 5, 4)
    with pytest.raises(ValueError, match="l <= K"):
        ops.flash_probe(q, c, l=6)
    with pytest.raises(ValueError, match="l >= 1"):
        ops.flash_probe(q, c, l=0)
    cand = jnp.broadcast_to(c, (10, 5, 4))
    with pytest.raises(ValueError, match="l <= C"):
        ops.flash_probe_grouped(q, cand, l=6)


# --- grouped (posting-list scan) variant ----------------------------------

def test_grouped_matches_per_query_topk():
    """Each query scores its own candidate block."""
    b, cn, d, l = 37, 53, 11, 9
    kq, kc = jax.random.split(jax.random.PRNGKey(5))
    q = jax.random.normal(kq, (b, d))
    cand = jax.random.normal(kc, (b, cn, d))
    idx, v = ops.flash_probe_grouped(q, cand, l=l, block_b=16, block_c=16)
    for i in range(b):
        idx_ref, v_ref = ref.probe_ref(q[i:i + 1], cand[i], l)
        assert np.array_equal(np.asarray(idx[i]), np.asarray(idx_ref[0]))
        np.testing.assert_allclose(np.asarray(v[i]), np.asarray(v_ref[0]),
                                   rtol=1e-5, atol=1e-5)


def test_grouped_shared_candidates_match_flash_probe():
    """Broadcasting one candidate set across queries reduces the grouped
    kernel to the shared-centroid kernel."""
    q, c = _data(24, 32, 8, seed=11)
    cand = jnp.broadcast_to(c, (24, 32, 8))
    gi, gv = ops.flash_probe_grouped(q, cand, l=6, block_b=8, block_c=16)
    si, sv = ops.flash_probe(q, c, l=6, block_n=8, block_k=16)
    assert np.array_equal(np.asarray(gi), np.asarray(si))
    np.testing.assert_allclose(np.asarray(gv), np.asarray(sv),
                               rtol=1e-5, atol=1e-5)


# --- heuristics entries ----------------------------------------------------

def test_probe_blocks_fit_budget():
    for (n, k, d, l) in [(256, 64, 32, 8), (100_000, 4096, 128, 64),
                         (8, 8, 8, 8), (1 << 20, 1 << 16, 256, 100)]:
        bn, bk = heuristics.choose_probe_blocks(n, k, d, l)
        assert bn >= 8 and bk >= 128
        budget = int(heuristics.TPU_V5E.vmem_bytes * 0.7)
        l_pad = ((max(1, l) + 7) // 8) * 8
        assert heuristics.probe_footprint(bn, bk, l_pad, d, 4) <= budget


def test_scan_blocks_fit_budget_and_shape():
    for (b, c, d, l) in [(64, 512, 24, 8), (1024, 1152, 64, 8),
                         (8, 128, 8, 8), (4096, 8192, 128, 100)]:
        bb, bc = heuristics.choose_scan_blocks(b, c, d, l)
        assert bb >= 8 and bc >= 128
        budget = int(heuristics.TPU_V5E.vmem_bytes * 0.7)
        l_pad = ((max(1, l) + 7) // 8) * 8
        assert heuristics.scan_footprint(bb, bc, l_pad, d, 4) <= budget



@pytest.mark.parametrize("pairs,k,width,d,l", [
    (32, 1024, 3000, 128, 10), (4096, 1024, 3000, 128, 10),
    (32768, 1024, 4096, 128, 10), (64, 8, 40, 16, 5),
    (1 << 20, 256, 65536, 512, 100)])
def test_list_scan_blocks_fit_budget(pairs, k, width, d, l):
    """``G`` is a sublane multiple, at most one MXU width and no more than
    the pairs; ``B_W`` a tile no wider than the width; both fit the
    planner's VMEM budget; one query (32 pairs over 1,024 lists) keeps
    the smallest group."""
    g, bw = heuristics.choose_list_scan_blocks(pairs, k, width, d, l)
    assert g % 8 == 0 and 8 <= g <= min(128, max(8, pairs))
    assert bw == 8 or bw in heuristics._CANDIDATE_TILES
    assert bw <= max(8, -(-width // 8) * 8)
    budget = int(heuristics.TPU_V5E.vmem_bytes * 0.7)
    l_pad = ((max(1, l) + 7) // 8) * 8
    assert heuristics.list_scan_footprint(g, bw, l_pad, d, 4) <= budget
    if pairs <= 32:
        assert g == 8

# --- list-major scan (probed lists streamed from the store) ---------------

_PAD = 1e15


def _list_store(counts, cap, d, seed, dup=False):
    """A padded store laid out as ``index.store`` lays it out: list ``j``
    holds ``counts[j]`` rows in slots ``[0, counts[j])``, padding rows at
    the far sentinel with id -1. ``dup`` repeats one row across slots and
    lists, so scores tie exactly."""
    rng = np.random.default_rng(seed)
    k = len(counts)
    x = np.full((k, cap, d), _PAD, np.float32)
    ids = np.full((k, cap), -1, np.int32)
    nxt = 0
    row = rng.normal(size=d).astype(np.float32)
    for j, n in enumerate(counts):
        x[j, :n] = rng.normal(size=(n, d)) * 2.0
        if dup:
            x[j, :n:2] = row
        ids[j, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return x, ids


def _probed_topk_oracle(q, probe, x, ids, counts, topk):
    """Brute force over each query's probed lists, in (rank, slot) order:
    float64 distances, exact ties to the lower position; -1 where the
    lists hold fewer than ``topk`` rows."""
    out_i = np.full((len(q), topk), -1, np.int64)
    out_d = np.full((len(q), topk), np.inf)
    for i, lists in enumerate(probe):
        cand = [(j, s) for j in lists for s in range(counts[j])]
        dist = np.array([np.sum((x[j, s].astype(np.float64) - q[i]) ** 2)
                         for j, s in cand])
        order = np.argsort(dist, kind="stable")[:topk]
        out_i[i, :len(order)] = [ids[cand[o]] for o in order]
        out_d[i, :len(order)] = dist[order]
    return out_i, out_d


# (B, K, counts, cap, nprobe, topk, G, B_W, hot list, duplicate rows)
LIST_SCAN = {
    "ragged-empty-lists": (10, 12, [0, 5, 37, 0, 12, 1, 30, 0, 8, 22, 3, 16],
                           40, 4, 5, 8, 16, False, False),
    "hot-list-over-G": (21, 8, [9, 14, 30, 2, 17, 25, 6, 11],
                        32, 3, 6, 8, 8, True, False),
    "width-not-tile-multiple": (6, 5, [37, 21, 40, 3, 29],
                                40, 2, 7, 8, 16, False, False),
    "one-query": (1, 9, [4, 17, 0, 26, 9, 13, 2, 31, 8],
                  32, 4, 10, 8, 8, False, False),
    "exact-ties": (7, 6, [12, 9, 16, 7, 14, 10],
                   16, 3, 8, 8, 8, False, True),
    "fewer-rows-than-topk": (5, 6, [2, 0, 1, 3, 0, 2],
                             8, 3, 6, 8, 8, False, False),
}


@pytest.mark.parametrize("case", list(LIST_SCAN))
def test_list_scan_matches_probed_brute_force(case):
    """``ivf._scan_lists`` (inversion into query groups, the
    ``flash_scan_lists`` kernel, the per-query merge) against brute force
    over each query's probed lists: ragged and empty lists, a hot list
    probed by more than ``G`` queries (several segments), a width that is
    not a multiple of the tile, one query, exact ties (lower (rank, slot)
    wins) and lists with fewer rows than ``topk`` (id -1)."""
    from repro.index import ivf
    b, k, counts, cap, nprobe, topk, g, bw, hot, dup = LIST_SCAN[case]
    d = 16
    rng = np.random.default_rng(len(case))
    x, ids = _list_store(counts, cap, d, seed=b + k, dup=dup)
    if hot:     # every query probes list 0 first
        probe = np.stack([np.r_[0, 1 + rng.permutation(k - 1)[:nprobe - 1]]
                          for _ in range(b)])
        assert b > g
    else:
        probe = np.stack([rng.permutation(k)[:nprobe] for _ in range(b)])
    q = (rng.normal(size=(b, d)) * 2.0).astype(np.float32)
    if dup:
        q[:] = q[:1]
    width = cap
    got_i, got_d = ivf._scan_lists(
        jnp.asarray(q), jnp.asarray(probe, jnp.int32),
        jnp.asarray(counts, jnp.int32),
        (jnp.asarray(x), jnp.asarray(ids)), kind="padded", topk=topk,
        width=width, ps=0, nsh=1, g=g, bw=bw, interpret=True)
    got_i, got_d = np.asarray(got_i), np.asarray(got_d)
    ref_i, ref_d = _probed_topk_oracle(q, probe, x, ids, counts, topk)
    filled = ref_i >= 0
    assert np.array_equal(got_i < 0, ~filled)
    # an unfilled slot reads as a padding row: finite, astronomically far
    assert np.all(np.isfinite(got_d)) and np.all(got_d[~filled] > 1e29)
    if dup:
        assert np.array_equal(got_i, ref_i)
    tol = float(f32_score_tol(jnp.asarray(q), jnp.asarray(
        x[x[:, :, 0] < _PAD])))
    assert_topk_match(np.where(filled, got_i, -1), np.where(filled, got_d, 0),
                      np.where(filled, ref_i, -1), np.where(filled, ref_d, 0),
                      tol=tol, rtol=0)
