"""The benchmark's CPU tests: ``bench`` imports from the repo root (the
program from ``src``), and
each cell can be run at a size the CPU holds, its device check skipped."""
from __future__ import annotations

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the cells' sizes cut to what the CPU runs in seconds (kernels in
# interpret mode); every other setting is the cell's own
TINY = {
    "kmeans_paper": {},
    "ivf_sift1m": {"n": 8192, "d": 32, "k": 32, "nprobe": 4,
                   "query_batch": 16, "n_centers": 32, "build_iters": 3},
    "fit_jobs": {"n": 4096, "k": 16, "d": 32, "iters": 3},
    "backlog": {"request_rows": 16, "pool_requests": 8, "check_requests": 2},
    "open_loop": {"rate": 100, "pool_queries": 512, "check_requests": 32},
}

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny(name: str) -> dict:
    """Cell ``name`` as ``bench.cells`` loads it, at a CPU size."""
    from bench import cells
    cell = copy.deepcopy(cells.load_cell(name))
    cell["config"].update(TINY[cell["workload"]["config"]])
    cell["workload"]["params"].update(TINY[cell["workload"]["driver"]])
    return cell


@pytest.fixture
def tiny_cell():
    return tiny
