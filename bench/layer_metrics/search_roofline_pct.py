"""Search units' share of the chip's roofline, over the traced window.

The least time of every unit of the window (``bench.counts.ivf_search``,
from the lists its queries probe and their real rows) over the time the
device was busy in the window. Taken over the whole unit, not per kernel
name.
"""


def read(run):
    tr, rec = run["trace"], run["record"]
    if not tr or not tr["busy_s"] or not rec.get("least_time_s"):
        return None
    return 100.0 * rec["least_time_s"] / tr["busy_s"]
