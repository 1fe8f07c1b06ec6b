"""Operations and bytes of one exact Lloyd iteration, from its shapes.

The count is the algorithm's work, the same whatever implements it:
the ``N x K x d`` distance product (``2NKd`` operations), and one read
of the points, one read of the centroids and one write of the new ones
(float32), and one write of the assignments (int32). It leaves out any
second pass over the points, extra one-hot products, and the passes a
float32 product takes at full precision.
"""
from __future__ import annotations


def iteration(n: int, k: int, d: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one Lloyd iteration."""
    return 2.0 * n * k * d, 4.0 * (n * d + 2 * k * d + n)


def least_time_s(n: int, k: int, d: int, peaks: dict) -> float:
    """The least time one iteration could take on the chip: the larger of
    its operations over peak FLOP/s and its bytes over peak bytes/s."""
    flops, nbytes = iteration(n, k, d)
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
