#!/usr/bin/env python3
"""Per-layer metrics read from the program's own record, for one cell.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

The cell is set up as ``bench/run.py`` sets it up, then driven for three
windows of ``--seconds`` each: with the program's tracing (``repro.obs``)
off, with it on, and with it on under the profiler. The program's record
is cleared at each window's start and read at its end. Each window
reports the cell's end-to-end numbers (the first two windows give the
cost of tracing) and every metric of ``PROGRAM_METRICS`` whose reader
finds something; the profiled window also reports the cell's own
per-layer metrics, as a ``--trace 1`` run does. The profiled window is
reduced with ``bench.stages`` (``stage_s``, idle gaps named by program
spans), and a search cell's record gains ``real_rows``.

The last line of standard output is one JSON object: ``correct`` (the
cell's check, on the last window's answers), ``setup_s``, ``windows``
(``off``, ``on``, ``profiled``: ``e2e`` and ``metrics`` each), and the
profiled window's ``stage_s``, ``idle_gaps``, program ``counters`` and
``real_rows``. Without a TPU it exits 1. ``bench/run.py`` does not call
this script; it stands until the harness collects these metrics itself.
"""
import contextlib
import gc
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
MODES = ("off", "on", "profiled")
PROGRAM_METRICS = ("gather_device_pct", "gather_useful_pct",
                   "engine_host_ms_per_unit", "update_device_pct")


def real_rows(cell: dict, st: dict) -> int | None:
    """The real rows of the distinct lists each unit of the window
    probes, summed over its units, from the exact top-``nprobe`` probe
    (the byte term of ``bench.counts.ivf_search``). A backlog request is
    one whole unit when its rows fill the unit; None for any other cell."""
    import jax.numpy as jnp
    import numpy as np
    from bench.reference import topk as ref_topk
    cfg = cell["config"]
    if (cell["workload"]["driver"] != "backlog"
            or st["p"]["request_rows"] != cfg["query_batch"]):
        return None
    cents = jnp.asarray(st["index"].centroids, jnp.float32)
    counts = np.asarray(st["index"].counts)
    per = {}
    for i, _ids, _dists in st["done"]:
        if i not in per:
            probed, _ = ref_topk.topk(jnp.asarray(st["pool"][i]), cents,
                                      k=cfg["nprobe"])
            per[i] = int(counts[np.unique(np.asarray(probed))].sum())
    return sum(per[i] for i, _ids, _dists in st["done"])


def window(cell: dict, drv, st: dict, seconds: float, mode: str, *,
           peaks: dict | None, trace_dir: str = TRACE_DIR) -> dict:
    """One window with tracing ``mode``; returns the run as the metric
    readers take it, with the program's record under ``program``."""
    from bench import stages
    from bench import trace as tr
    from repro import obs
    spans = tr.Spans(tracing=mode == "profiled")
    ctx = (tr.capture(trace_dir) if mode == "profiled"
           else contextlib.nullcontext())
    if mode != "off":
        obs.enable()
    obs.reset()
    try:
        with ctx:
            with spans(tr.WINDOW_SPAN):
                rec = drv.window(st, seconds, spans)
        program = obs.snapshot()
    finally:
        obs.disable()
        obs.reset()
    run = {"cell": cell, "record": rec, "trace": None, "spans": spans,
           "peaks": peaks, "program": program}
    if peaks is not None:
        rec.update(drv.layer_record(st, rec))
    rows = real_rows(cell, st)
    if rows is not None:
        rec["real_rows"] = rows
    if mode == "profiled":
        run["trace"] = stages.reduce(stages.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return run


def metrics(run: dict, names) -> dict:
    """The metrics of ``names`` whose readers find something in ``run``."""
    from bench import cells
    out = {}
    for name in names:
        v = cells.layer_reader(name)(run)
        if v is not None:
            out[name] = v
    return out


def execute(cell: dict, seed: int, seconds: float, *, peaks: dict | None,
            t0: float, trace_dir: str = TRACE_DIR) -> dict:
    """The three windows of ``cell`` and its check; the result object."""
    from bench import cells, compare
    from bench import trace as tr
    drv = cells.driver(cell["workload"]["driver"])
    st = drv.setup(cell, seed, tr.Spans())
    setup_s = time.perf_counter() - t0
    windows, last = {}, None
    for mode in MODES:
        last = window(cell, drv, st, seconds, mode, peaks=peaks,
                      trace_dir=trace_dir)
        names = list(PROGRAM_METRICS)
        if mode == "profiled":
            names += [m["name"] for m in cell["per_layer"]]
        windows[mode] = {"e2e": last["record"]["e2e"],
                         "metrics": metrics(last, names)}
    drv.release(st)
    gc.collect()
    ok, _rows = compare.judge(drv.check(st, seed), cell["workload"]["limits"])
    reduced = last["trace"] or {}
    return {"correct": bool(ok), "setup_s": setup_s, "windows": windows,
            "stage_s": reduced.get("stage_s"),
            "idle_gaps": reduced.get("idle_gaps"),
            "counters": last["program"]["counters"],
            "real_rows": last["record"].get("real_rows")}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_PLAN_CACHE"] = "off"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cells
    from bench import run as harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    cell = cells.load_cell(args.workload)
    try:
        harness.configure_jax()
        device = harness.device_info(cell["workload"]["chips"])
    except (harness.NoChip, KeyError, RuntimeError) as e:
        print(f"program_trace: {e}", file=sys.stderr, flush=True)
        return 1
    out = execute(cell, args.seed, args.seconds,
                  peaks=cells.peaks(device["kind"]), t0=t0)
    print(json.dumps(dict(out, workload=args.workload, seed=args.seed,
                          device=device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
