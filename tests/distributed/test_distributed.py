"""Multi-device equivalence tests (subprocess with 8 fake CPU devices —
the main test process must keep seeing exactly 1 device)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.slow
def test_distributed_equivalences():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT,
         env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"   # the worker's fake devices are CPUs
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "distributed", "_dist_worker.py")],
        capture_output=True, text=True, env=env, timeout=900)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr[-4000:])
    assert r.returncode == 0, "distributed worker failed"
    assert "FAIL" not in r.stdout
    assert r.stdout.count("PASS") >= 9
