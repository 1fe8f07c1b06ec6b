"""Lloyd iterations' share of the chip's roofline, over the traced window.

The least time of the window's iterations (``bench.counts.lloyd``: the
larger of 2NKd over peak FLOP/s and the bytes of one pass over points
and centroids over peak bytes/s, times the iterations) over the time
the device was busy in the window. Taken over the whole step, not per
kernel name, so it holds whichever kernels run the step.
"""
from bench.counts import lloyd


def read(run):
    tr, rec = run["trace"], run["record"]
    if not tr or not tr["busy_s"] or not rec.get("lloyd"):
        return None
    c = rec["lloyd"]
    least = c["iterations"] * lloyd.least_time_s(c["n"], c["k"], c["d"],
                                                 run["peaks"])
    return 100.0 * least / tr["busy_s"]
