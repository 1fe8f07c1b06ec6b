"""Each cell's control, the plain reference put in the program's place
at the precision below the configuration's (three bfloat16 passes), at a
size the CPU holds.

The search control must fail the cell's own limits. The fit control's
step is spelled out in three bfloat16 products on the CPU, which round
closer than the chip's ``Precision.HIGH`` does: at the cells' own sizes
the chip's control fails their limits (``PERF.md``), while here it is
held to what it must show at any size, points sent to a centroid that
is not the nearest."""
from __future__ import annotations

import pytest

from bench import control

SEEDS = (11, 12, 13)


@pytest.mark.parametrize("name", ["search_backlog", "search_open_loop"])
def test_search_control_fails_the_cell_limits(tiny_cell, name):
    cell = tiny_cell(name)
    # the configuration's own width, and as many queries as a run checks
    cell["config"]["d"] = 128
    p = cell["workload"]["params"]
    p["check_requests"] = 1024 // p.get("request_rows", 1)
    for seed in SEEDS:
        r = control.readings(cell, seed)
        assert not r["passes_limits"], r


@pytest.mark.parametrize("name", ["fit_k1024_d128", "fit_k65536_d512"])
def test_fit_control_moves_near_ties(tiny_cell, name):
    cell = tiny_cell(name)
    cell["workload"]["params"].update(n=65536, k=256, d=128)
    for seed in SEEDS:
        r = control.readings(cell, seed)
        assert set(cell["workload"]["limits"]) <= set(r["numbers"])
        assert r["numbers"]["assign_gap"] > 0, r
        assert r["numbers"]["assign_mismatch"] > 0, r
