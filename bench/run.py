#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``bench/workloads/<cell>.json``) names its configuration and
its traffic driver; the driver makes the data on the device from
``--seed``, builds and warms the program (set-up), drives it for
``--seconds`` (the window), and hands what it produced to the plain
reference (``bench/reference``). With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``, each number that decided
``correct`` beside its limit; the same numbers are the last lines of
standard error. Without a TPU, with fewer chips than the cell asks for,
or without the program's sources beside ``bench/``, it exits non-zero
and prints no result.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    pass


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_jax() -> None:
    """Persistent compilation cache at a fixed path inside the checkout,
    holding every program (the small ones too, so that set-up after the
    first run loads and never compiles)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_info(chips: int) -> dict:
    """The platform JAX found; raises ``NoChip`` without a TPU or with
    fewer chips than the cell asks for."""
    import jax
    from bench import cells
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    cells.peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class _CompileCounter:
    def __init__(self):
        import jax
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += duration


def execute(cell: dict, seed: int, seconds: float, trace: bool, *,
            t0: float, device: dict) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    from bench import cells, compare
    from bench import trace as tr

    drv = cells.driver(cell["workload"]["driver"])
    compiles = _CompileCounter()
    spans = tr.Spans(tracing=trace)
    state = drv.setup(cell, seed, spans)
    setup_s = time.perf_counter() - t0
    for part, s in state["setup_parts"].items():
        _log(f"setup {part}: {s:.3f} s")
    _log(f"setup total: {setup_s:.3f} s, {compiles.n} compiles "
         f"({compiles.s:.3f} s)")

    n_compiled = compiles.n
    ctx = tr.capture(TRACE_DIR) if trace else contextlib.nullcontext()
    with ctx:
        with spans(tr.WINDOW_SPAN):
            rec = drv.window(state, seconds, spans)
    _log(f"window: {rec['window_s']:.3f} s, {compiles.n - n_compiled} "
         "compiles inside it")
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=stats.get("peak_bytes_in_use"))

    run = {"cell": cell, "record": rec, "trace": None, "spans": spans,
           "peaks": None}
    if trace:
        run["record"].update(drv.layer_record(state, rec))
        run["peaks"] = cells.peaks(device["kind"])
        run["trace"] = tr.reduce(tr.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if run["trace"]:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
    drv.release(state)
    gc.collect()
    t_check = time.perf_counter()
    numbers = drv.check(state, seed)
    ok, rows = compare.judge(numbers, cell["workload"]["limits"])
    _log(f"reference check: {time.perf_counter() - t_check:.3f} s")
    for name in sorted(set(numbers) - set(cell["workload"]["limits"])):
        _log(f"not compared {name}: {numbers[name]!r}")

    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            v = cells.layer_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(rec["e2e"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": bool(ok), "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace and run["trace"]:
        out["breakdown"] = {k: run["trace"][k]
                            for k in ("device_ops", "idle_gaps")}
    out["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                     for r in rows}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_PLAN_CACHE"] = "off"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cells
    try:
        if not cells.listed(args.workload):
            raise KeyError(f"no workload {args.workload!r} in BENCHMARK.json")
        cell = cells.load_cell(args.workload)
        import repro  # noqa: F401  (the program under test)
    except (OSError, KeyError, ImportError) as e:
        _log(f"bench: {e}")
        return 2
    try:
        configure_jax()
        device = device_info(cell["workload"]["chips"])
    except (NoChip, KeyError, RuntimeError) as e:
        _log(f"bench: {e}")
        return 1
    _log(f"bench: {args.workload} seed {args.seed} on {device['kind']} "
         f"x{device['count']}")
    out = execute(cell, args.seed, args.seconds, bool(args.trace),
                  t0=_T0, device=device)
    for name, c in out["checks"].items():
        _log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
