"""``bench/program_trace.py`` on the CPU: three windows of a cell at a
CPU size, the program's record read in the traced ones. The CPU profile
has no TPU plane, so only the metrics read from the program's spans and
counters come out here."""
from __future__ import annotations

import time

from bench import program_trace as pt


def test_search_windows_read_the_program_record(tiny_cell, tmp_path):
    cell = tiny_cell("search_backlog")
    out = pt.execute(cell, 2147483701, 0.3, peaks=None,
                     t0=time.perf_counter(), trace_dir=str(tmp_path / "t"))
    assert out["correct"]
    assert set(out["windows"]) == {"off", "on", "profiled"}
    assert all(w["e2e"]["search_qps"] > 0 for w in out["windows"].values())
    assert out["windows"]["off"]["metrics"] == {}
    for mode in ("on", "profiled"):
        m = out["windows"][mode]["metrics"]
        assert set(m) == {"gather_useful_pct", "engine_host_ms_per_unit"}
        assert 0 < m["gather_useful_pct"] <= 100
        assert m["engine_host_ms_per_unit"] > 0
    counters = out["counters"]
    assert counters["ivf.units"] > 0
    assert 0 < out["real_rows"] <= counters["ivf.gathered_rows"]
    assert out["stage_s"] is None          # no TPU plane in a CPU trace


def test_fit_windows_record_no_program_metric(tiny_cell, tmp_path):
    cell = tiny_cell("fit_k1024_d128")
    out = pt.execute(cell, 2147483701, 0.3, peaks=None,
                     t0=time.perf_counter(), trace_dir=str(tmp_path / "t"))
    assert out["correct"]
    assert all(w["metrics"] == {} for w in out["windows"].values())
    assert out["counters"] == {} and out["real_rows"] is None
