"""FlashLloyd fused kernel vs composed references: assignments, sufficient
statistics, inertia, ragged shapes, empty clusters, bf16, fused-vs-two-
pass Lloyd-trajectory equivalence, and the exact three-term bf16 split of
the statistics product (interpret mode on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KMeansConfig, init_centroids, lloyd_step, make_kmeans_fn
from repro.core import heuristics as H
from repro.kernels import ops, ref
from repro.kernels.flash_lloyd import flash_lloyd_raw, split_bf16x3
from tests.conftest import assert_assignments_match

try:  # hypothesis is optional: deterministic tests below run without it
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # pragma: no cover - CI installs requirements-dev.txt
    hypothesis = st = None


def _data(n, k, d, dtype=jnp.float32, seed=0):
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (n, d), dtype)
    c = jax.random.normal(kc, (k, d), dtype)
    return x, c


def _check(x, c, block_n, block_k, tol=1e-4):
    """Fused outputs vs assign_ref + dense-one-hot oracle on fused's own
    assignments (sidesteps numerical near-tie index divergence)."""
    a, s, cnt, j = ops.flash_lloyd_step(x, c, block_n=block_n,
                                        block_k=block_k)
    a_ref, m_ref = ref.assign_ref(x, c)
    assert_assignments_match(x.astype(jnp.float32), c.astype(jnp.float32),
                             a, a_ref, tol=max(tol, 1e-3))
    s_ref, cnt_ref = ref.update_dense_onehot_ref(x, a, c.shape[0])
    assert np.array_equal(np.asarray(cnt), np.asarray(cnt_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=tol, atol=tol)
    return a, s, cnt, j


# ragged N/K (non-multiples of any block), tiny and padded-heavy shapes
SHAPES = [
    (16, 4, 2), (100, 7, 3), (256, 64, 32), (1000, 37, 19),
    (513, 100, 33), (2048, 512, 64), (333, 17, 257),
]


@pytest.mark.parametrize("n,k,d", SHAPES)
def test_sweep_f32(n, k, d):
    x, c = _data(n, k, d)
    a, s, cnt, j = _check(x, c, block_n=128, block_k=64)
    _, m_ref = ref.assign_ref(x, c)
    np.testing.assert_allclose(float(j), float(jnp.sum(m_ref)),
                               rtol=1e-4)
    # mass conservation: every real point counted exactly once
    np.testing.assert_allclose(np.asarray(cnt).sum(), n)
    np.testing.assert_allclose(np.asarray(s).sum(0), np.asarray(x.sum(0)),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n,k,d", [(256, 64, 32), (100, 7, 3)])
def test_sweep_bf16(n, k, d):
    x, c = _data(n, k, d, jnp.bfloat16)
    a, s, cnt, j = ops.flash_lloyd_step(x, c, block_n=64, block_k=32)
    # counts are integral regardless of input dtype
    assert np.asarray(cnt).sum() == n
    # statistics accumulate in f32: compare against the f32 oracle on the
    # fused assignments with bf16-input tolerance
    s_ref, cnt_ref = ref.update_dense_onehot_ref(x, a, k)
    assert np.array_equal(np.asarray(cnt), np.asarray(cnt_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-2, atol=2e-2)


def test_block_shape_invariance():
    x, c = _data(300, 50, 16)
    outs = [ops.flash_lloyd_step(x, c, block_n=bn, block_k=bk)
            for bn, bk in [(8, 8), (128, 128), (256, 64)]]
    a0, s0, c0, j0 = outs[0]
    for a1, s1, c1, j1 in outs[1:]:
        assert_assignments_match(x, c, a1, a0)
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(c0), np.asarray(c1))
        np.testing.assert_allclose(float(j0), float(j1), rtol=1e-5)


def test_empty_clusters():
    """A far-away centroid attracts no points: zero count, zero sum row."""
    x, _ = _data(200, 1, 5)
    c = jnp.concatenate([x[:7], jnp.full((1, 5), 100.0)])
    a, s, cnt, _ = ops.flash_lloyd_step(x, c, block_n=64, block_k=8)
    assert not bool(jnp.any(a == 7))
    assert float(cnt[7]) == 0.0
    assert np.all(np.asarray(s)[7] == 0.0)


def test_matches_two_pass_step():
    """One fused lloyd_step == one two-pass lloyd_step (same blocks math)."""
    x, _ = _data(700, 1, 24, seed=3)
    c0 = init_centroids(jax.random.PRNGKey(1), x, 40, "random")
    cfg_f = KMeansConfig(k=40, step_impl="fused")
    cfg_t = KMeansConfig(k=40, step_impl="two_pass")
    cf, af, jf = lloyd_step(x, c0, cfg_f)
    ct, at, jt = lloyd_step(x, c0, cfg_t)
    assert_assignments_match(x, c0, af, at)
    np.testing.assert_allclose(np.asarray(cf), np.asarray(ct),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(jf), float(jt), rtol=1e-5)


def test_update_impl_fused_alias():
    """update_impl="fused" routes to the same fused kernel as step_impl."""
    x, _ = _data(300, 1, 8, seed=5)
    c0 = init_centroids(jax.random.PRNGKey(2), x, 12, "random")
    c_a, a_a, j_a = lloyd_step(x, c0, KMeansConfig(k=12, update_impl="fused"))
    c_b, a_b, j_b = lloyd_step(x, c0, KMeansConfig(k=12, step_impl="fused"))
    np.testing.assert_allclose(np.asarray(c_a), np.asarray(c_b))
    assert np.array_equal(np.asarray(a_a), np.asarray(a_b))


def test_contradictory_config_raises():
    cfg = KMeansConfig(k=4, update_impl="fused", step_impl="two_pass")
    with pytest.raises(ValueError, match="contradicts"):
        cfg.resolved_step_impl(100, 8, 4)
    cfg = KMeansConfig(k=4, update_impl="fused", assign_impl="ref")
    with pytest.raises(ValueError, match="assign_impl"):
        cfg.resolved_step_impl(100, 8, 4)
    cfg = KMeansConfig(k=4, step_impl="fused", assign_impl="ref")
    with pytest.raises(ValueError, match="assign_impl"):
        cfg.resolved_step_impl(100, 8, 4)
    cfg = KMeansConfig(k=4, step_impl="fused", update_impl="scatter")
    with pytest.raises(ValueError, match="update_impl"):
        cfg.resolved_step_impl(100, 8, 4)
    with pytest.raises(ValueError, match="step impl"):
        KMeansConfig(k=4, step_impl="nope").resolved_step_impl(100, 8, 4)


def test_fit_trajectory_equivalence():
    """Full fused fit == full two-pass fit in f32 (identical trajectories)."""
    kc, kx = jax.random.split(jax.random.PRNGKey(9))
    centers = jax.random.normal(kc, (6, 10)) * 6.0
    x = (centers[jax.random.randint(kx, (900,), 0, 6)]
         + jax.random.normal(jax.random.fold_in(kx, 1), (900, 10)) * 0.3)
    key = jax.random.PRNGKey(11)
    st_f = make_kmeans_fn(
        KMeansConfig(k=6, max_iters=8, step_impl="fused"))(key, x)
    st_t = make_kmeans_fn(
        KMeansConfig(k=6, max_iters=8, step_impl="two_pass"))(key, x)
    assert int(st_f.iteration) == int(st_t.iteration)
    assert np.array_equal(np.asarray(st_f.assignments),
                          np.asarray(st_t.assignments))
    np.testing.assert_allclose(np.asarray(st_f.centroids),
                               np.asarray(st_t.centroids),
                               rtol=1e-5, atol=1e-5)
    # The paths round the sums differently, so the fits' centroids differ
    # in the last bits, and the float32 inertia (||x||^2 ~ 360 against
    # distances ~ 0.9) magnifies that to ~1e-5. So each fit's inertia is
    # held to the other path's at the same centroids, and the objectives
    # of the two fits to each other in float64.
    x64 = np.asarray(x, np.float64)
    objective = []
    for st, other in ((st_f, "two_pass"), (st_t, "fused")):
        _, _, j_other = lloyd_step(x, st.centroids,
                                   KMeansConfig(k=6, step_impl=other))
        np.testing.assert_allclose(float(st.inertia), float(j_other),
                                   rtol=1e-5)
        c64 = np.asarray(st.centroids, np.float64)
        objective.append(((x64[:, None] - c64[None]) ** 2).sum(-1)
                         .min(1).sum())
    np.testing.assert_allclose(objective[0], objective[1], rtol=1e-5)


def test_chunked_fused_equals_monolithic():
    """The out-of-core driver on the fused path reproduces the monolithic
    iteration (one HBM stream per chunk instead of three)."""
    from repro.core import ChunkedKMeans
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (1000, 12))
    c0 = init_centroids(jax.random.PRNGKey(1), x, 7, "random")
    cfg = KMeansConfig(k=7, max_iters=1, step_impl="fused")
    c_mono, _, j_mono = lloyd_step(x, c0, cfg)
    ck = ChunkedKMeans(cfg, chunk_size=256)
    c_chunk, j_chunk = ck.iterate(np.asarray(x), c0)
    np.testing.assert_allclose(np.asarray(c_mono), np.asarray(c_chunk),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(j_mono), float(j_chunk), rtol=1e-5)


@pytest.mark.parametrize("use_jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("decade", [-20, -10, -1, 0, 1, 10, 20])
def test_split_bf16x3_is_exact(decade, use_jit):
    """hi + mid + lo reassembles every float32 bit for bit, at magnitudes
    1e-20 to 1e20 of both signs, eagerly and under jit (a compiler that
    folded the float32 -> bf16 -> float32 round trip would lose lo)."""
    rng = np.random.default_rng(decade + 100)
    mag = 10.0 ** rng.uniform(decade - 0.5, decade + 0.5, (64, 128))
    x = (mag * rng.choice([-1.0, 1.0], mag.shape)).astype(np.float32)
    split = jax.jit(split_bf16x3) if use_jit else split_bf16x3
    terms = split(jnp.asarray(x))
    assert all(t.dtype == jnp.bfloat16 for t in terms)
    hi, mid, lo = (np.asarray(t).astype(np.float64) for t in terms)
    assert np.array_equal(hi + mid + lo, x.astype(np.float64))
    assert np.count_nonzero(lo) > x.size // 2   # three terms, not two


def _rel_err(s, s64, used):
    return float((np.abs(np.asarray(s, np.float64) - s64)[used]
                  / np.abs(s64)[used]).max())


@pytest.mark.parametrize("n,k,seed", [(2048, 512, 0), (1024, 256, 1),
                                      (4096, 1024, 2)])
def test_fused_sums_keep_float32_on_offset_data(n, k, seed):
    """On 1000 + N(0, 1) points in clusters of a few, the fused sums match
    float64 segment sums to 1e-6 relative; the same sums over two terms
    of the split read ~4e-6, so the bound would catch a drop below
    float32."""
    rng = np.random.default_rng(seed)
    x = (1000.0 + rng.standard_normal((n, 128))).astype(np.float32)
    c = x[rng.choice(n, k, replace=False)]
    a, s, cnt, _ = ops.flash_lloyd_step(jnp.asarray(x), jnp.asarray(c),
                                        block_n=256, block_k=128)
    a, used = np.asarray(a), np.asarray(cnt) > 0
    s64 = np.zeros((k, 128))
    np.add.at(s64, a, x.astype(np.float64))
    assert _rel_err(s, s64, used) <= 1e-6
    hi, mid, _lo = split_bf16x3(jnp.asarray(x))
    two = np.asarray(hi.astype(jnp.float32) + mid.astype(jnp.float32))
    s_two = np.zeros((k, 128))
    np.add.at(s_two, a, two.astype(np.float64))
    assert _rel_err(s_two, s64, used) > 1e-6


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_matches_lloyd_stats_ref(dtype, tol):
    """Assignments and counts equal ``lloyd_stats_ref``'s and the inertia
    and sums agree to the input's precision: the split leaves sweep 1
    alone, and bf16 points keep their one-pass product."""
    kc, kl, kn = jax.random.split(jax.random.PRNGKey(21), 3)
    centers = jax.random.normal(kc, (24, 32)) * 6.0
    x = (centers[jax.random.randint(kl, (1500,), 0, 24)]
         + 0.3 * jax.random.normal(kn, (1500, 32))).astype(dtype)
    c = x[::63][:24]
    a, s, cnt, j = ops.flash_lloyd_step(x, c, block_n=256, block_k=128)
    a_r, s_r, cnt_r, j_r = ref.lloyd_stats_ref(x, c)
    assert np.array_equal(np.asarray(a), np.asarray(a_r))
    assert np.array_equal(np.asarray(cnt), np.asarray(cnt_r))
    np.testing.assert_allclose(float(j), float(j_r), rtol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_r),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,k,d,impl,blocks", [
    (8_388_608, 1024, 128, "fused", (1024, 256)),
    (1_048_576, 65_536, 512, "two_pass", None)])
def test_fit_cell_plans_hold(n, k, d, impl, blocks):
    """The split's footprint leaves each fit cell's plan as it was: the
    K=1024, d=128 cells (one chip, and each shard of four) fused at
    blocks (1024, 256), the K=65,536, d=512 cell two-pass."""
    assert H.choose_step_impl(n, k, d) == impl
    if blocks is not None:
        blk = H.choose_blocks(n, k, d)
        assert (blk.fused_block_n, blk.fused_block_k) == blocks


def _dots(jaxpr):
    """Every ``dot_general`` in ``jaxpr`` and the jaxprs nested in it, as
    ``(operand dtypes, precision)``."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append((tuple(str(v.aval.dtype) for v in eqn.invars),
                        eqn.params["precision"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _dots(sub)
    return out


HIGHEST = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("dtype,dots", [
    (jnp.float32, [(("float32", "float32"), HIGHEST)]
     + 4 * [(("bfloat16", "bfloat16"), None)]),
    (jnp.bfloat16, 3 * [(("bfloat16", "bfloat16"), None)])],
    ids=["f32", "bf16"])
def test_kernel_products_by_dtype(dtype, dots):
    """float32 points: the distances at HIGHEST (sweep 1), then three bf16
    products for the sums and one for the counts (sweep 2); bf16 points:
    one bf16 product each, as the operands' own precision."""
    x, c = _data(256, 64, 32, dtype)
    closed = jax.make_jaxpr(functools.partial(
        flash_lloyd_raw, block_n=128, block_k=64, k_actual=64,
        n_actual=256, interpret=True))(x, c)
    assert _dots(closed.jaxpr) == dots


if hypothesis is not None:
    @hypothesis.settings(max_examples=25, deadline=None)
    @hypothesis.given(n=st.integers(1, 300), k=st.integers(1, 80),
                      d=st.integers(1, 24), seed=st.integers(0, 10_000))
    def test_property_fused_sufficient_statistics(n, k, d, seed):
        x, c = _data(n, k, d, seed=seed)
        a, s, cnt, j = ops.flash_lloyd_step(x, c, block_n=32, block_k=16)
        s_ref, cnt_ref = ref.update_scatter_ref(x, a, k)
        assert np.array_equal(np.asarray(cnt), np.asarray(cnt_ref))
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-4, atol=1e-4)
        dmat = np.asarray(ref.pairwise_sq_dists(x, c))
        np.testing.assert_allclose(float(j), float(dmat.min(axis=1).sum()),
                                   rtol=1e-3, atol=1e-3)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_fused_sufficient_statistics():
        pass
