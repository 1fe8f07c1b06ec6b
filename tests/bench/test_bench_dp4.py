"""The four-chip fit cell ``fit_dp4_k1024_d128`` at a CPU size, and the
readers of its two per-layer metrics.

``_dp4_worker.py`` runs once, in a subprocess with 4 fake CPU devices
(this process keeps its one device): whole runs of the cell (device
check skipped), sound and with a fault planted under the sharded step;
the sharded reference's numbers beside ``compare.step_numbers`` on the
gathered arrays; the data made shard by shard beside ``bench.data.blobs``;
the traced window's program counters; the control. The readers are
checked here on small hand-made reduced traces.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import ROOT

from bench import cells
from bench.counts import lloyd

EPS = float(np.finfo(np.float32).eps)


@pytest.fixture(scope="module")
def lines():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "bench",
                                      "_dp4_worker.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = [json.loads(ln) for ln in r.stdout.splitlines()
           if ln.startswith("{")]
    return {c: [o for o in out if o["check"] == c]
            for c in {o["check"] for o in out}}


def test_sound_runs_are_correct(lines):
    assert len(lines["sound"]) == 2
    for o in lines["sound"]:
        assert o["correct"], o
        assert o["metrics"] == ["lloyd_iter_ms", "setup_s"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_under_the_sharded_step_is_not_correct(lines, fault):
    (o,) = [o for o in lines["fault"] if o["fault"] == fault]
    assert not o["correct"], o


@pytest.mark.parametrize("step", ["program", "altered", "half"])
def test_sharded_numbers_equal_step_numbers_on_gathered_arrays(lines, step):
    """The same numbers, up to the order of float32 sums (the sharded
    reference adds per-device sums in float64)."""
    (o,) = [o for o in lines["numbers"] if o["step"] == step]
    sh, wh = o["sharded"], o["whole"]
    assert set(sh) == set(wh)
    assert sh["assign_mismatch"] == wh["assign_mismatch"]
    assert sh["assign_gap"] == pytest.approx(wh["assign_gap"], abs=4 * EPS)
    for name in ("update_gap", "inertia_gap"):
        assert sh[name] == pytest.approx(wh[name], rel=1e-3, abs=8 * EPS)


def test_data_is_blobs_made_shard_by_shard(lines):
    (o,) = lines["data"]
    assert o["equal"] and o["shards"] == 4 and o["shard_rows"] == [1024]
    assert 1024 % o["chunk"] == 0


def test_initial_centroids_are_the_one_chip_jobs(lines):
    (o,) = lines["init"]
    assert o["equal"]


def test_traced_window_records_the_program_counters(lines):
    (o,) = lines["counters"]
    rec = o["layer_record"]
    assert o["chips"] == 4 and o["steps"] > 0
    assert rec == {"lloyd.sharded_steps": o["steps"],
                   "lloyd.allreduce_bytes": o["steps"] * o["psum_bytes"]}


def test_control_moves_near_ties(lines):
    assert len(lines["control"]) == 3
    for o in lines["control"]:
        assert o["numbers"]["assign_gap"] > 0, o
        assert o["numbers"]["assign_mismatch"] > 0, o


# --- the readers ------------------------------------------------------------

PEAKS = {"flops_per_s": 197e12,
         "hbm_bytes_per_s": 819e9}


def _run(ops, busy_s=20.0, iterations=100, chips=4):
    return {"trace": {"window_s": 20.1, "busy_s": busy_s,
                      "device_ops": ops, "idle_gaps": []},
            "record": {"lloyd": {"n": 1 << 25, "k": 1024, "d": 128,
                                 "iterations": iterations, "chips": chips}},
            "peaks": PEAKS}


def test_dp_roofline_takes_one_chips_share_of_the_points():
    read = cells.layer_reader("lloyd_dp_roofline_pct")
    run = _run([["flash_lloyd_step.1", 19.9]])
    least = 100 * lloyd.least_time_s(1 << 23, 1024, 128, PEAKS)
    assert read(run) == pytest.approx(100 * least / 20.0)
    run["record"]["lloyd"]["chips"] = 1
    assert read(run) == pytest.approx(400 * least / 20.0)
    assert read(dict(run, trace=None)) is None


def test_allreduce_share_sums_all_reduce_ops():
    read = cells.layer_reader("allreduce_pct.fit")
    run = _run([["flash_lloyd_step.1", 19.9], ["all-reduce", 0.03],
                ["all-reduce-start.1", 0.01], ["all-reduce-done.1", 0.01],
                ["fusion.all-reduce", 0.5]])
    assert read(run) == pytest.approx(100 * 0.05 / 20.0)


def test_allreduce_share_is_none_without_an_all_reduce():
    read = cells.layer_reader("allreduce_pct.fit")
    assert read(_run([["flash_lloyd_step.1", 19.9], ["fusion", 0.1]])) is None
    assert read(dict(_run([]), trace=None)) is None
