import os

# Keep unit tests on the single real CPU device (the dry-run sets its own
# fake-device flag in a separate process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Hermetic KernelPlanner: never read a stale ~/.cache plan file into the
# suite (a pre-heuristics-change cache would silently serve old block
# shapes) and never mutate the developer's real cache as a side effect.
# Tests that exercise persistence pass an explicit tmp_path cache_path.
os.environ.setdefault("REPRO_PLAN_CACHE", "off")

import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def tracing():
    """Program tracing (``repro.obs``) on and empty for one test, then
    off and empty again."""
    from repro import obs
    obs.reset()
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()
        obs.reset()


def assert_assignments_match(x, c, a_test, a_ref, tol=1e-3):
    """Assignments may differ only on numerical near-ties."""
    import jax.numpy as jnp
    from repro.kernels.ref import pairwise_sq_dists
    d = np.asarray(pairwise_sq_dists(x, c))
    a_test = np.asarray(a_test)
    a_ref = np.asarray(a_ref)
    bad = []
    for i in np.nonzero(a_test != a_ref)[0]:
        if abs(d[i, a_test[i]] - d[i, a_ref[i]]) > tol:
            bad.append(i)
    assert not bad, f"{len(bad)} true mismatches, first {bad[:5]}"


def assert_topk_match(ids, dists, ids_ref, dists_ref, tol=1e-3, rtol=1e-4):
    """Result lists may differ only by swaps of numerical near-ties:
    every position must either agree on the id or sit inside a run of
    reference distances closer than ``tol``, and each row must hold the
    same id set."""
    ids, dists = np.asarray(ids), np.asarray(dists)
    ids_ref, dists_ref = np.asarray(ids_ref), np.asarray(dists_ref)
    np.testing.assert_allclose(dists, dists_ref, rtol=rtol, atol=tol)
    bad = []
    for r in range(ids.shape[0]):
        for j in np.nonzero(ids[r] != ids_ref[r])[0]:
            if abs(dists[r, j] - dists_ref[r, j]) > tol:
                bad.append((r, j))
        if set(ids[r].tolist()) != set(ids_ref[r].tolist()):
            bad.append((r, "set"))
    assert not bad, f"{len(bad)} true mismatches, first {bad[:5]}"


def f32_score_tol(q, c, ulps=8):
    """Stated tolerance for a squared-distance score (or its
    ``||c||^2 - 2 q.c`` part) that two XLA graphs evaluate with different
    f32 reduction orders: ``ulps`` float32 epsilons of the largest
    magnitude the expanded form ``||q||^2 + ||c||^2 - 2 q.c`` passes
    through."""
    q = np.asarray(q, np.float64)
    c = np.asarray(c, np.float64)
    qn = np.sum(q * q, axis=1).max()
    cn = np.sum(c * c, axis=1).max()
    mag = qn + cn + 2.0 * np.sqrt(qn * cn)
    return float(ulps * np.finfo(np.float32).eps * mag)
