"""The reduction from a profiler trace to busy time, idle gaps and op
totals (``bench.trace``), on a hand-worked trace and on a small trace
recorded on a TPU v5e (three ``KMeans.fit`` jobs with host sleeps
between them)."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000   # ns


def _trace(ops, spans):
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": [
             [n, a * MS, (b - a) * MS] for n, a, b in ops]}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": [
             ["bench." + n, a * MS, (b - a) * MS] for n, a, b in spans]}]}]}


def test_hand_worked_trace():
    # a while op (W) holding two ops; E runs past the window's end
    ops = [("%A = f32[] fusion()", 0, 10), ("%W = while()", 20, 60),
           ("%C = f32[] fusion()", 20, 30), ("%D = custom-call()", 40, 60),
           ("%E = f32[] copy()", 95, 110)]
    spans = [("window", 0, 100), ("pump", 10, 30), ("take", 50, 90)]
    r = tr.reduce(_trace(ops, spans))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.055)        # 10 + 40 + 5
    assert tr.idle_pct(r) == pytest.approx(45.0)
    # leaves only: the while op's time is its body's, not counted twice
    assert r["device_ops"] == [["D", pytest.approx(0.020)],
                               ["A", pytest.approx(0.010)],
                               ["C", pytest.approx(0.010)],
                               ["E", pytest.approx(0.005)]]
    # [60, 95] lies mostly in take; [10, 20] wholly in pump
    assert r["idle_gaps"] == [["take", pytest.approx(0.035)],
                              ["pump", pytest.approx(0.010)]]


def test_gap_outside_every_span_is_no_span():
    ops = [("%A = fusion()", 0, 10), ("%B = fusion()", 40, 50)]
    spans = [("window", 0, 50), ("take", 10, 15)]
    r = tr.reduce(_trace(ops, spans))
    assert r["idle_gaps"] == [["no span", pytest.approx(0.030)]]


def test_no_window_or_no_device_op_reads_nothing():
    assert tr.reduce(_trace([("%A = f()", 0, 1)], [("pump", 0, 5)])) is None
    assert tr.reduce(_trace([], [("window", 0, 5)])) is None
    assert tr.idle_pct(None) is None


def test_recorded_v5e_trace():
    with open(os.path.join(DATA, "trace_v5e_fit.json")) as f:
        t = json.load(f)
    r = tr.reduce(t)
    ops = t["planes"][0]["lines"][0]["events"]
    win = [e for e in t["planes"][1]["lines"][0]["events"]
           if e[0] == "bench.window"][0]
    lo, hi = win[1], win[1] + win[2]
    # busy time by a 1-ns timeline of the window, independent of _union
    line = np.zeros(hi - lo, bool)
    for _n, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            line[a - lo:b - lo] = True
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["busy_s"] == pytest.approx(line.sum() / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    names = [n for n, _s in r["device_ops"]]
    assert names[0].startswith("flash_lloyd_step")
    assert not any(n.startswith("while") for n in names)
    gaps = [s for _n, s in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # the three 10 ms host sleeps between jobs are the longest gaps, and
    # no bench span covers them
    assert [n for n, _s in r["idle_gaps"][:3]] == ["no span"] * 3
    assert all(s > 0.010 for s in gaps[:3])
