"""Device time per program stage and idle gaps named by program spans
(``bench.stages``), and the readers of the per-layer metrics built on
them, on hand-worked traces and on traces recorded on a TPU v5e."""
from __future__ import annotations

import json
import os

import pytest

from bench import cells
from bench import stages
from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000   # ns


def _trace(ops, spans):
    """``ops``: (name, start_ms, end_ms, scope); ``spans``: (event name,
    start_ms, end_ms), names with their ``bench.``/``repro.`` prefix."""
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": [
             [n, a * MS, (b - a) * MS, sc] for n, a, b, sc in ops]}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": [
             [n, a * MS, (b - a) * MS] for n, a, b in spans]}]}]}


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.mark.parametrize("scope,stage", [
    ("jit(_ivf_search)/ivf.gather/jit(gather)/gather", "ivf.gather"),
    ("jit(lloyd_step)/lloyd.update/jit(sort_inverse_update)/sort",
     "lloyd.update"),
    ("jit(_ivf_search_routed)/ivf.probe/ivf.scan/dot", "ivf.scan"),
    ("jit(flash_assign)/pallas_call", None),
    ("", None),
])
def test_stage_of_a_scope_path(scope, stage):
    assert stages.stage(scope) == stage


def test_hand_worked_stages_and_gaps():
    # W is a while op holding C and D: stage time counts leaves only
    ops = [("%A = fusion()", 0, 10, "jit(s)/ivf.probe/add"),
           ("%W = while()", 20, 60, "jit(s)/ivf.scan/while"),
           ("%C = fusion()", 20, 30, "jit(s)/ivf.scan/dot"),
           ("%D = custom-call()", 40, 60, "jit(s)/ivf.scan/pallas_call"),
           ("%G = gather()", 70, 80, "jit(s)/ivf.gather/gather"),
           ("%E = copy()", 95, 110, "")]
    spans = [("bench.window", 0, 100), ("bench.pump", 10, 20),
             ("repro.engine.form", 12, 18),
             ("bench.take", 60, 95), ("repro.engine.complete", 61, 64),
             ("repro.engine.complete", 80, 95)]
    r = stages.reduce(_trace(ops, spans))
    assert r["stage_s"] == {"ivf.gather": pytest.approx(0.010),
                            "ivf.probe": pytest.approx(0.010),
                            "ivf.scan": pytest.approx(0.030)}
    # [80, 95]: wholly in complete inside take -> the innermost;
    # [10, 20]: all of it in pump, 6 of 10 in form -> form, the innermost
    # span over more than half; [60, 70]: take covers it all, complete
    # only 3 of 10 -> take
    assert r["idle_gaps"] == [["engine.complete", pytest.approx(0.015)],
                              ["engine.form", pytest.approx(0.010)],
                              ["take", pytest.approx(0.010)]]


def test_gap_under_program_span_nested_in_benchmark_span():
    ops = [("%A = f()", 0, 10, ""), ("%B = f()", 50, 60, "")]
    spans = [("bench.window", 0, 60), ("bench.take", 5, 55),
             ("repro.engine.take", 6, 54),
             ("repro.engine.complete", 9, 52)]
    r = stages.reduce(_trace(ops, spans))
    assert r["idle_gaps"] == [["engine.complete", pytest.approx(0.040)]]


def test_recorded_fit_trace_reads_as_before():
    """The trace without scopes or program spans (recorded before either
    existed) gives ``bench.trace.reduce``'s numbers exactly."""
    t = _load("trace_v5e_fit.json")
    old, new = tr.reduce(t), stages.reduce(t)
    for key in ("window_s", "busy_s", "device_ops"):
        assert new[key] == old[key]
    assert new["idle_gaps"] == old["idle_gaps"]
    assert new["stage_s"] == {}


def test_recorded_search_trace():
    """A few ``search_backlog`` units recorded on a v5e with the
    program's tracing on: every device op of the search program falls in
    a stage, the gather takes device time, and the benchmark's own
    numbers are those ``bench.trace.reduce`` reads from the same trace."""
    t = _load("trace_v5e_search.json")
    r = stages.reduce(t)
    old = tr.reduce(stages._bench_view(t))
    for key in ("window_s", "busy_s", "device_ops"):
        assert r[key] == old[key]
    assert {"ivf.probe", "ivf.gather", "ivf.scan"} <= set(r["stage_s"])
    assert all(v > 0 for v in r["stage_s"].values())
    assert sum(r["stage_s"].values()) <= r["busy_s"] * (1 + 1e-9)
    hosts = {e[0] for p in t["planes"] if not p["name"].startswith("/device")
             for line in p["lines"] for e in line["events"]}
    assert {"repro.engine.dispatch", "repro.engine.complete",
            "bench.window"} <= hosts
    named = {n for n, _s in r["idle_gaps"]}
    assert named and not named & {"window"}


def _varint(v):
    out = b""
    while True:
        out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
        v >>= 7
        if not v:
            return out


def _msg(*fields):
    """A serialized protobuf message from ``(number, value)`` pairs: ints
    as varints, str and bytes length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_device_scopes_from_a_serialized_xspace():
    """``tf_op`` read from each op's event metadata (inline or interned as
    a stat-metadata name); an op with none takes its first scoped
    operand's from the program's HLO in the ``/host:metadata`` plane."""
    hlo = _msg((1, _msg((1, "jit_f"), (3, _msg(
        (1, "main"),
        (2, _msg((1, "a"), (7, _msg((2, "jit(f)/ivf.gather/gather"))),
                 (35, 1))),
        (2, _msg((1, "b"), (35, 2), (36, _varint(1)))),
        (2, _msg((1, "c"), (35, 3), (36, _varint(7)))))))))

    def stat(sid, field, v):        # XEventMetadata.stats: one XStat
        return 5, _msg((1, sid), (field, v))

    def entry(k, v):                # one entry of a protobuf map
        return _msg((1, k), (2, v))

    host = _msg((2, "/host:metadata"),
                (4, entry(1, _msg((1, 1), (2, "jit_f(77)"),
                                  stat(9, 6, hlo)))),
                (5, entry(9, _msg((1, 9), (2, "Hlo Proto")))))
    device = _msg(
        (2, "/device:TPU:0"),
        (4, entry(1, _msg((1, 1), (2, "%a = f()"), (4, "a"),
                          stat(20, 5, "jit(f)/ivf.gather/gather:"),
                          stat(21, 3, 77)))),
        (4, entry(2, _msg((1, 2), (2, "%b = f()"), (4, "b"),
                          stat(21, 3, 77)))),
        (4, entry(3, _msg((1, 3), (2, "%c = f()"), (4, "c"),
                          stat(21, 3, 77)))),
        (4, entry(4, _msg((1, 4), (2, "%d = f()"), (4, "d"),
                          stat(20, 7, 30)))),
        (5, entry(20, _msg((1, 20), (2, "tf_op")))),
        (5, entry(21, _msg((1, 21), (2, "program_id")))),
        (5, entry(30, _msg((1, 30), (2, "jit(f)/ivf.scan/dot:")))))
    scopes = stages.device_scopes(_msg((1, host), (1, device)))
    assert scopes == {"/device:TPU:0": {
        "%a = f()": "jit(f)/ivf.gather/gather:",
        "%b = f()": "jit(f)/ivf.gather/gather",   # from its operand a
        "%c = f()": "",                           # no scoped operand
        "%d = f()": "jit(f)/ivf.scan/dot:"}}
    assert [stages.stage(s) for s in scopes["/device:TPU:0"].values()] == [
        "ivf.gather", "ivf.gather", None, "ivf.scan"]


def test_load_keeps_program_and_benchmark_host_spans(tmp_path):
    import jax
    from repro import obs
    spans = tr.Spans(tracing=True)
    obs.enable()
    try:
        with tr.capture(str(tmp_path)):
            with spans("window"):
                with obs.span("engine.take", rid=3):
                    jax.numpy.ones(4).block_until_ready()
    finally:
        obs.disable()
        obs.reset()
    t = stages.load(str(tmp_path))
    names = [e[0] for p in t["planes"] for line in p["lines"]
             for e in line["events"]]
    assert "bench.window" in names and "repro.engine.take" in names
    # the benchmark's own loader still reads only its spans
    old = [e[0] for p in tr.load(str(tmp_path))["planes"]
           for line in p["lines"] for e in line["events"]]
    assert "bench.window" in old and "repro.engine.take" not in old


def _run(trace=None, record=None, program=None):
    return {"trace": trace, "record": record or {}, "program": program,
            "spans": tr.Spans(), "peaks": None}


@pytest.mark.parametrize("metric,run,value", [
    ("gather_device_pct",
     _run(trace={"busy_s": 2.0, "stage_s": {"ivf.gather": 1.5}}), 75.0),
    ("update_device_pct",
     _run(trace={"busy_s": 20.0, "stage_s": {"lloyd.update": 0.5}}), 2.5),
    ("gather_useful_pct",
     _run(record={"real_rows": 50},
          program={"counters": {"ivf.gathered_rows": 1000}}), 5.0),
    ("engine_host_ms_per_unit",
     _run(program={"counters": {"ivf.units": 2}, "spans": [
         ("engine.form", 0, 1_000_000, "engine.pump", {}),
         ("engine.dispatch", 0, 2_000_000, "engine.pump", {}),
         ("engine.settle", 0, 1_500_000, "engine.pump", {}),
         ("engine.take", 0, 9_000_000, None, {})]}), 1.5),
])
def test_reader_values(metric, run, value):
    assert cells.layer_reader(metric)(run) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["gather_device_pct", "update_device_pct",
                                    "gather_useful_pct",
                                    "engine_host_ms_per_unit"])
def test_readers_find_nothing_without_the_program_record(metric):
    """A run of a program without these spans, scopes and counters (or a
    harness that does not collect them) reads nothing and raises nothing."""
    read = cells.layer_reader(metric)
    assert read(_run()) is None
    assert read(_run(trace={"busy_s": 1.0, "window_s": 1.0,
                            "device_ops": [], "idle_gaps": []},
                     record={"least_time_s": 0.1})) is None


def test_engine_host_ms_reads_nothing_when_spans_were_dropped():
    """Spans pushed out of the program's full record would leave the sum
    short of the units the counter holds."""
    program = {"counters": {"ivf.units": 2}, "dropped": 1, "spans": [
        ("engine.form", 0, 1_000_000, "engine.pump", {})]}
    assert cells.layer_reader("engine_host_ms_per_unit")(
        _run(program=program)) is None
