"""Operations and bytes of one IVF search unit, from its shapes.

A unit of ``B`` queries against ``K`` lists of ``d``-wide float32 rows
must at least read the queries and the centroids once, and every real
row of the distinct lists its queries probe once (no padding), and it
scores each query against the centroids and against every row it
probes:

- bytes = 4·d·(real rows of the distinct probed lists) + 4·K·d + 4·B·d
- operations = 2·B·K·d + 2·d·(rows probed, summed over the queries)

Any implementation of the unit does at least this much, so a roofline
share taken from it cannot pass 100%.
"""
from __future__ import annotations

import numpy as np


def unit(probed: np.ndarray, counts: np.ndarray, k: int, d: int
         ) -> tuple[float, float]:
    """``(operations, bytes)`` of a unit; ``probed`` (B, nprobe) are the
    lists each query probes and ``counts`` (K,) the real rows per list."""
    probed = np.asarray(probed)
    counts = np.asarray(counts, np.float64)
    b = probed.shape[0]
    distinct = np.unique(probed)
    flops = 2.0 * b * k * d + 2.0 * d * float(counts[probed].sum())
    nbytes = 4.0 * d * float(counts[distinct].sum()) + 4.0 * k * d \
        + 4.0 * b * d
    return flops, nbytes


def least_time_s(probed, counts, k: int, d: int, peaks: dict) -> float:
    """The least time the unit could take on the chip."""
    flops, nbytes = unit(probed, counts, k, d)
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
