#!/usr/bin/env python3
"""Readings of a cell's control: the plain reference put in the
program's place at the nearest precision below the configuration's
(``bf16x3``, three bfloat16 passes, for float32 at full precision),
judged by the same numbers and limits as a run.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

``--fault`` reads a fit cell's planted faults instead (``update_gap``
takes its upper reading from them, as the control leaves the update
exact). Runs on the machine it is started on, at the cell's own sizes, and
prints one JSON line per seed: the numbers and whether they pass the
cell's limits (a sound limit makes every control line fail). The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell: dict, seed: int, precision: str = "bf16x3",
             fault: str | None = None) -> dict:
    from bench import cells, compare
    drv = cells.driver(cell["workload"]["driver"])
    numbers = (drv.control(cell, seed, precision, fault) if fault
               else drv.control(cell, seed, precision))
    ok, _rows = compare.judge(numbers, cell["workload"]["limits"])
    return {"seed": seed, "precision": precision, "fault": fault,
            "passes_limits": ok, "numbers": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="bf16x3")
    ap.add_argument("--fault", default=None,
                    help="a fit cell's planted fault (unchanged, half, "
                         "altered) in place of the lower precision")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import cells, run
    run.configure_jax()
    cell = cells.load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps(dict(readings(cell, seed, args.precision,
                                       args.fault),
                              workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
