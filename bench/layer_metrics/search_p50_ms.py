"""Median latency of the window's requests, each from its due time to
its ids being on the host."""
import numpy as np


def read(run):
    lat = run["record"].get("latency_ms")
    if lat is None or not len(lat):
        return None
    return float(np.median(lat))
