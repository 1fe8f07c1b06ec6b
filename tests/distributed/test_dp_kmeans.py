"""Data-parallel Lloyd through ``KMeans(cfg, mesh)`` on 4 fake CPU devices.

``_dp_worker.py`` runs once, in a subprocess (the test process keeps its
one device), and saves what each test below checks: the sharded step
against the plain reference and against one device's step, the sharded
fit against ``make_distributed_kmeans``, the sharded random init against
``random_init``, the K-sharded step and the sharded ``predict``.

Assignments may differ only on near-ties: a point's two candidate
centroids within ``f32_score_tol`` of each other. Centroids are compared
on the clusters that no such swap touched.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from tests.conftest import f32_score_tol

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
IMPLS = ("fused", "two_pass")


@pytest.fixture(scope="module")
def res(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("dp") / "res.npz")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "distributed",
                                      "_dp_worker.py"), path],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return dict(np.load(path))


def _same_step(res, c, a, c_ref, a_ref, atol=1e-4):
    """Assignments equal up to near-ties; centroids allclose where no
    near-tie moved a point."""
    x, c0 = res["x"], res["c0"]
    dist = ((x[:, None, :].astype(np.float64) - c0[None]) ** 2).sum(-1)
    diff = np.nonzero(a != a_ref)[0]
    gap = np.abs(dist[diff, a[diff]] - dist[diff, a_ref[diff]])
    assert np.all(gap <= f32_score_tol(x, c0)), gap.max()
    moved = np.zeros(c0.shape[0], bool)
    moved[a[diff]] = moved[a_ref[diff]] = True
    assert moved.sum() < c0.shape[0] // 2
    np.testing.assert_allclose(c[~moved], c_ref[~moved], rtol=1e-5,
                               atol=atol)


@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_iterate_matches_reference(res, impl):
    _same_step(res, res[f"mesh_{impl}_c"], res[f"mesh_{impl}_a"],
               res["ref_c"], res["ref_a"])
    assert float(res[f"mesh_{impl}_j"]) == pytest.approx(
        float(res["ref_j"]), rel=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_iterate_matches_one_device(res, impl):
    _same_step(res, res[f"mesh_{impl}_c"], res[f"mesh_{impl}_a"],
               res[f"one_{impl}_c"], res[f"one_{impl}_a"])
    assert float(res[f"mesh_{impl}_j"]) == pytest.approx(
        float(res[f"one_{impl}_j"]), rel=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_iterate_returns_assignments_sharded_like_points(res, impl):
    assert str(res[f"mesh_{impl}_a_spec"]) in ("PartitionSpec('data',)",
                                               "PartitionSpec(('data',),)")
    assert int(res[f"mesh_{impl}_a_shards"]) == 4
    assert res[f"mesh_{impl}_a"].shape == (res["x"].shape[0],)


@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_fit_matches_make_distributed_kmeans(res, impl):
    """The same key: the sharded init draws ``random_init``'s rows, so the
    two loops start, and run, alike."""
    np.testing.assert_allclose(res[f"fit_{impl}_c"], res[f"dist_{impl}_c"],
                               rtol=1e-6, atol=1e-6)
    assert float(res[f"fit_{impl}_j"]) == pytest.approx(
        float(res[f"dist_{impl}_j"]), rel=1e-6)
    assert int(res[f"fit_{impl}_iteration"]) == 6


@pytest.mark.parametrize("seed", range(4))
def test_sharded_random_init_takes_random_init_rows(res, seed):
    assert np.array_equal(res[f"init_mesh_{seed}"], res[f"init_one_{seed}"])


def test_sharded_random_init_gathers_no_points(res):
    """One all-reduce (the (k, d) rows) and no other collective: no
    device is sent another's shard."""
    assert str(res["init_collectives"]) == "all-reduce"


def test_k_sharded_iterate_matches_one_device(res):
    assert str(res["kshard_axis"]) == "model"
    _same_step(res, res["kshard_c"], res["kshard_a"], res["one_two_pass_c"],
               res["one_two_pass_a"])


def test_sharded_predict_matches_one_device(res):
    x, c0 = res["x"], res["c0"]
    a, a_one = res["predict_mesh"], res["predict_one"]
    dist = ((x[:, None, :].astype(np.float64) - c0[None]) ** 2).sum(-1)
    diff = np.nonzero(a != a_one)[0]
    assert np.all(np.abs(dist[diff, a[diff]] - dist[diff, a_one[diff]])
                  <= f32_score_tol(x, c0))
