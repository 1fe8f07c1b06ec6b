"""``bench/run.py`` refuses to run without a TPU or without the program
beside it, and a run's result object carries exactly the keys of the
benchmark's contract (a tiny cell on the CPU, device check skipped)."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

from conftest import CPU_DEVICE, ROOT

from bench import run

ARGS = ["--workload", "fit_k1024_d128", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_beside_no_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_refuses_a_workload_benchmark_json_does_not_list():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "search_open_loop", *ARGS[2:]], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no workload" in p.stderr


def test_result_object_has_the_contract_keys(tiny_cell):
    cell = tiny_cell("fit_k1024_d128")
    out = run.execute(cell, 2147483659, 0.5, False,
                      t0=time.perf_counter(), device=dict(CPU_DEVICE))
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"lloyd_iter_ms", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(out["checks"]) == set(cell["workload"]["limits"])
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(out)
