#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop search cell to find its knee:
the highest rate at which completions keep pace with arrivals and the
queue does not grow over the window.

    python3 bench/sweep_knee.py --workload search_open_loop --seed 5 \
        --seconds 10 --rates 800 1200 1600 2000

Builds and warms the cell once, then runs its window at each rate in
turn and prints one JSON line per rate: completions per second, the
median latency of the first and the last quarter of the requests (a
queue that grows makes the last quarter wait longer), p50 and p99. The
cell's own rate is then set by hand at about 0.8 x the knee; the
benchmark's runs never search for a rate.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="search_open_loop")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_PLAN_CACHE"] = "off"
    from bench import cells, run
    from bench import trace as tr
    run.configure_jax()
    cell = copy.deepcopy(cells.load_cell(args.workload))
    p = cell["workload"]["params"]
    p["pool_queries"] = max(p["pool_queries"],
                            int(max(args.rates) * args.seconds * 1.6) + 64)
    drv = cells.driver(cell["workload"]["driver"])
    st = drv.setup(cell, args.seed, tr.Spans())
    for rate in args.rates:
        p["rate"] = rate
        rec = drv.window(st, args.seconds, tr.Spans())
        lat = rec["latency_ms"]
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate": rate, "requests": len(lat),
            "completed_per_s": len(lat) / rec["window_s"],
            "window_s": rec["window_s"],
            "p50_first_quarter_ms": float(np.median(lat[:q])),
            "p50_last_quarter_ms": float(np.median(lat[-q:])),
            "p50_ms": float(np.median(lat)),
            "p99_ms": float(np.percentile(lat, 99)),
            "unit_rows_mean": rec["queries"] / max(1, rec["units"]),
            "lag_p99_ms": float(np.percentile(rec["lag_ms"], 99))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
