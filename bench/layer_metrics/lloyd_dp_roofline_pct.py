"""Data-parallel Lloyd iterations' share of one chip's roofline, over
the traced window of a cell whose points are sharded over ``chips``.

The least time of the window's iterations at one chip's share of the
points (``bench.counts.lloyd`` at N/chips rows: each chip's part of the
work, at its own peaks) over the mean busy time of the chips
(``bench.trace.reduce`` averages busy time over the devices). Taken over
the whole step, the all-reduce included, not per kernel name.
"""
from bench.counts import lloyd


def read(run):
    tr, rec = run["trace"], run["record"]
    if not tr or not tr["busy_s"] or not rec.get("lloyd"):
        return None
    c = rec["lloyd"]
    least = c["iterations"] * lloyd.least_time_s(
        c["n"] // c.get("chips", 1), c["k"], c["d"], run["peaks"])
    return 100.0 * least / tr["busy_s"]
