"""Program tracing (``repro.obs``) and its sites in the engine, the IVF
search and the Lloyd step.

Off (the default) nothing is recorded and no profiler annotation is
made; on, spans nest by thread, keep their attributes, and reach the
profiler's trace as ``repro.<name>`` host events."""
from __future__ import annotations

import glob
import os
import re
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import KMeans, KMeansConfig
from repro.core.kmeans import lloyd_step
from repro.index import IVFIndex
from repro.serve.engine import SearchConfig, SearchEngine

K, D = 16, 16


def _blobs(seed, n, spread=6.0, noise=0.3):
    kc, ka, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
    centers = jax.random.normal(kc, (K, D)) * spread
    assign = jax.random.randint(ka, (n,), 0, K)
    return np.asarray(centers[assign]
                      + jax.random.normal(kn, (n, D)) * noise)


@pytest.fixture(scope="module")
def corpus():
    return _blobs(0, 1024), _blobs(7, 300)


def _engine(x, **index_kw):
    index = IVFIndex.build(x, k=K, max_iters=4, seed=0, **index_kw)
    return SearchEngine(index, SearchConfig(topk=5, nprobe=4,
                                            query_batch=32))


def _spans(name=None):
    return [s for s in obs.snapshot()["spans"]
            if name is None or s[0] == name]


# --- the module -------------------------------------------------------------

def test_off_records_nothing_and_makes_no_annotation(corpus, monkeypatch):
    def refuse(*_a, **_kw):
        raise AssertionError("a TraceAnnotation was made with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    obs.reset()
    assert not obs.enabled()
    x, q = corpus
    eng = _engine(x)
    eng.take(eng.submit(q[:40]))
    KMeans(KMeansConfig(k=4)).iterate(x[:64], x[:4])
    with obs.span("anything", rid=1) as sp:
        sp.set(rows=2)
    obs.count("anything", 5)
    assert obs.snapshot() == {"spans": [], "counters": {}, "dropped": 0}
    lat = eng.latency_stats()
    assert lat["dispatch_p50_ms"] == 0.0 and lat["complete_p99_ms"] == 0.0


def test_spans_nest_keep_attrs_and_reset_clears(tracing):
    with tracing.span("outer", rid=7):
        with tracing.span("inner", unit=1) as sp:
            sp.set(rows=3)
        with tracing.span("inner", unit=2):
            pass
    tracing.count("c")
    tracing.count("c", 4)
    snap = tracing.snapshot()
    by = [(n, p, a) for n, _t0, _t1, p, a in snap["spans"]]
    assert by == [("inner", "outer", {"unit": 1, "rows": 3}),
                  ("inner", "outer", {"unit": 2}),
                  ("outer", None, {"rid": 7})]
    (_n, t0, t1, _p, _a), outer = snap["spans"][0], snap["spans"][2]
    assert outer[1] <= t0 <= t1 <= outer[2]
    assert snap["counters"] == {"c": 5}
    tracing.reset()
    assert tracing.snapshot() == {"spans": [], "counters": {}, "dropped": 0}


def test_record_keeps_the_newest_spans(tracing):
    extra = 3
    for i in range(tracing.MAX_SPANS + extra):
        with tracing.span("s", i=i):
            pass
    snap = tracing.snapshot()
    assert len(snap["spans"]) == tracing.MAX_SPANS
    assert snap["dropped"] == extra
    assert snap["spans"][0][4] == {"i": extra}
    assert snap["spans"][-1][4] == {"i": tracing.MAX_SPANS + extra - 1}
    tracing.reset()
    assert tracing.snapshot()["dropped"] == 0


def test_span_is_recorded_when_its_body_raises(tracing):
    with pytest.raises(KeyError):
        with tracing.span("outer"):
            with tracing.span("failing"):
                raise KeyError("x")
    assert [(n, p) for n, _a, _b, p, _c in _spans()] == [
        ("failing", "outer"), ("outer", None)]
    with tracing.span("after"):
        pass
    assert _spans("after")[0][3] is None      # the stack unwound


def test_each_thread_has_its_own_parents(tracing):
    seen = threading.Barrier(2, timeout=10)

    def work(tag):
        with tracing.span("root", tag=tag):
            seen.wait()
            with tracing.span("leaf", tag=tag):
                seen.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    leaves = _spans("leaf")
    assert len(leaves) == 2 and all(s[3] == "root" for s in leaves)
    assert all(s[3] is None for s in _spans("root"))


def test_spans_reach_the_profiler_trace(tracing, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("engine.take", rid=7):
            jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = [e for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events
              if e.name == "repro.engine.take"]
    assert len(events) == 1
    assert ("rid", 7) in [(k, int(v)) for k, v in events[0].stats
                          if k == "rid"]


# --- the sites --------------------------------------------------------------

def test_request_spans_share_its_rid_in_order(corpus, tracing):
    x, q = corpus
    eng = _engine(x)
    r1 = eng.submit(q[:20])
    r2 = eng.submit(q[20:50])        # coalesces with r1, then splits
    eng.take(r1)
    eng.take(r2)
    spans = sorted(_spans(), key=lambda s: s[1])
    for rid in (r1, r2):
        mine = [s for s in spans if s[4].get("rid") == rid
                or rid in s[4].get("rids", ())]
        # submitted first, taken last; formed in between
        assert mine[0][0] == "engine.submit"
        assert max(mine, key=lambda s: s[2])[0] == "engine.take"
        assert "engine.form" in [s[0] for s in mine]
    # r2 is served by two units: both form spans name it
    forms = [s[4] for s in spans if s[0] == "engine.form"]
    assert [f["rids"] for f in forms] == [(r1, r2), (r2,)]
    assert [f["rows"] for f in forms] == [32, 18]
    assert [f["bucket"] for f in forms] == [32, 32]
    # every span of a unit carries the unit's number, from batches_formed
    for name in ("engine.dispatch", "engine.settle", "engine.complete"):
        assert sorted(s[4]["unit"] for s in spans if s[0] == name) == [1, 2]
    assert eng.batches_formed == 2
    # a unit's spans nest in the pump, a request's take holds the pump
    parents = {s[0]: s[3] for s in spans}
    assert parents["engine.dispatch"] == "engine.pump"
    assert parents["engine.pump"] == "engine.take"


@pytest.mark.parametrize("index_kw,list_major", [
    ({}, True), ({"codec": "q8"}, False),
    ({"store": "paged", "page_size": 8}, True)],
    ids=["padded-fp32", "padded-q8", "paged-fp32"])
def test_gathered_rows_per_unit(corpus, tracing, index_kw, list_major):
    """The q8 path gathers nprobe lists of the gather width per query;
    the fp32 paths take the list-major scan, count its units, and count
    the store rows its grid addresses (segments x the width's tiles)."""
    x, q = corpus
    eng = _engine(x, **index_kw)
    idx = eng.index
    width = idx._gather_width(5, 4)
    tracing.reset()
    sizes = [32, 7, 20]                 # buckets 32, 8, 32
    for n in sizes:
        eng.take(eng.submit(q[:n]))
    assert [s[4]["bucket"] for s in _spans("engine.form")] == [32, 8, 32]
    counters = tracing.snapshot()["counters"]
    assert counters["ivf.units"] == 3
    if not list_major:
        assert "ivf.list_scan_units" not in counters
        assert counters["ivf.gathered_rows"] == (32 + 8 + 32) * 4 * width
        return
    assert counters["ivf.list_scan_units"] == 3
    rows = 0
    for b in (32, 8, 32):
        _, _, g, bw = idx.plan_search(b, 5, 4)
        bw = index_kw.get("page_size") or min(bw, idx.cap)
        segs = -(-b * 4 // g) + min(K, b * 4)
        rows += segs * -(-width // bw) * bw
    assert counters["ivf.gathered_rows"] == rows


@pytest.mark.parametrize("step_impl,scopes", [
    ("fused", {"lloyd.fused", "lloyd.finalize"}),
    ("two_pass", {"lloyd.assign", "lloyd.update", "lloyd.finalize"})])
def test_lloyd_step_carries_stage_scopes(tracing, step_impl, scopes):
    """The Lloyd step names its device stages (``update_device_pct`` reads
    ``lloyd.update``) and records no host span."""
    x = _blobs(3, 256)
    cfg = KMeansConfig(k=8, step_impl=step_impl)
    text = jax.jit(lambda x, c: lloyd_step(x, c, cfg)).lower(
        x, x[:8]).as_text(debug_info=True)
    found = re.findall(r"lloyd\.(?:fused|assign|update|finalize)\b", text)
    assert set(found) == scopes
    KMeans(cfg).iterate(x, x[:8])
    assert _spans() == []


def test_sharded_iterate_counts_steps_and_allreduce_bytes():
    """On 4 fake CPU devices (a subprocess, ``_dp_worker.py --obs``): each
    sharded ``iterate`` adds 1 to ``lloyd.sharded_steps`` and the modelled
    ``stats_psum`` bytes to ``lloyd.allreduce_bytes``, and one device's
    step adds nothing; off, nothing is recorded. The step names its psum
    ``lloyd.allreduce`` beside the kernel and update scopes."""
    import json
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "distributed",
                                      "_dp_worker.py"), "--obs"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["off"] == {"spans": [], "counters": {}, "dropped": 0}
    assert out["psum_bytes"] == 2 * 4 * (16 * 32 + 16 + 1)
    assert out["counters"] == {"lloyd.sharded_steps": 3,
                               "lloyd.allreduce_bytes": 3 * out["psum_bytes"]}
    assert out["spans"] == 0
    assert "lloyd.allreduce" in out["scopes"]
    assert "lloyd.finalize" in out["scopes"]
