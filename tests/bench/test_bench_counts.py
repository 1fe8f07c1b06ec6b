"""Operation and byte counts from shapes (``bench.counts``), worked by
hand, and the roofline readers built on them."""
from __future__ import annotations

import numpy as np
import pytest

from bench import cells
from bench.counts import ivf_search, lloyd

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def test_lloyd_iteration_by_hand():
    # N=4, K=2, d=3: 2*4*2*3 = 48 operations; 4*(12 + 12 + 4) = 112 bytes
    assert lloyd.iteration(4, 2, 3) == (48.0, 112.0)


def test_lloyd_paper_regimes_are_compute_bound():
    # K=1024: 2.2 TFLOP against 4.3 GB; K=65,536: 70 TFLOP against 2.4 GB
    t = lloyd.least_time_s(8388608, 1024, 128, V5E)
    assert t == pytest.approx(2 * 8388608 * 1024 * 128 / 197e12)
    assert t == pytest.approx(0.011163, rel=1e-4)
    assert lloyd.least_time_s(1048576, 65536, 512, V5E) == pytest.approx(
        0.357, rel=1e-3)


def test_ivf_unit_by_hand():
    # K=4 lists of 10, 20, 0, 5 rows, d=2; two queries probing lists
    # (0, 1) and (1, 3): distinct lists 0, 1, 3 hold 35 rows, and the
    # queries probe 30 + 25 = 55 rows
    probed = np.array([[0, 1], [1, 3]])
    counts = np.array([10, 20, 0, 5])
    flops, nbytes = ivf_search.unit(probed, counts, k=4, d=2)
    assert flops == 2 * 2 * 4 * 2 + 2 * 2 * 55
    assert nbytes == 4 * 2 * 35 + 4 * 4 * 2 + 4 * 2 * 2


def test_ivf_unit_least_time_takes_the_larger_bound():
    probed = np.zeros((128, 32), int) + np.arange(32)
    counts = np.full(1024, 1000)
    flops, nbytes = ivf_search.unit(probed, counts, k=1024, d=128)
    assert ivf_search.least_time_s(probed, counts, 1024, 128, V5E) == \
        pytest.approx(max(flops / 197e12, nbytes / 819e9))


def test_roofline_readers():
    tr = {"busy_s": 2.0, "window_s": 2.5}
    run = {"trace": tr, "peaks": V5E, "record": {
        "lloyd": {"n": 8388608, "k": 1024, "d": 128, "iterations": 10},
        "least_time_s": 0.5}}
    pct = cells.layer_reader("lloyd_roofline_pct")(run)
    assert pct == pytest.approx(100 * 10 * 0.0111634 / 2.0, rel=1e-4)
    assert cells.layer_reader("search_roofline_pct")(run) == \
        pytest.approx(25.0)
    assert cells.layer_reader("device_idle_pct.fit")(run) == \
        pytest.approx(20.0)
    # nothing to read: no value, never a zero share
    run["trace"] = None
    for name in ("lloyd_roofline_pct", "search_roofline_pct",
                 "device_idle_pct.search"):
        assert cells.layer_reader(name)(run) is None


def test_peaks_table_refuses_an_unknown_device():
    assert cells.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        cells.peaks("cpu")
