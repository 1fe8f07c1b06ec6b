"""Plain Lloyd k-means step: the reference a fit's step is compared with.

One exact Lloyd step on float32 points under squared L2: every point
goes to its nearest centroid (the dense distance matrix, a block of rows
at a time), and each centroid becomes the mean of its points by a
scatter-add (an empty cluster keeps its centroid).

``precision`` is the precision of the distance matmul: ``"highest"``
(float32, the precision the configuration states) or ``"bf16x3"``, the
three-pass bfloat16 product: ``Precision.HIGH`` on a TPU, spelled out
elsewhere (the CPU computes every float32 product in full); it is the
control's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_BLOCK_BYTES = 1 << 29   # one block of the distance matrix


def _split_bf16(v):
    # reduce_precision, not a round trip through bfloat16, which XLA may
    # drop as excess precision
    hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (v - hi).astype(jnp.bfloat16)


def cross(x, c, precision: str):
    """``x @ c.T`` in float32 at the given precision."""
    dims = (((1,), (1,)), ((), ()))
    if precision == "highest":
        return jax.lax.dot_general(x, c, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    if precision == "bf16x3" and jax.default_backend() == "tpu":
        return jax.lax.dot_general(x, c, dims,
                                   precision=jax.lax.Precision.HIGH,
                                   preferred_element_type=jnp.float32)
    if precision == "bf16x3":
        (xh, xl), (ch, cl) = _split_bf16(x), _split_bf16(c)

        def dot(a, b):
            return jax.lax.dot_general(a, b, dims,
                                       preferred_element_type=jnp.float32)
        return dot(xh, ch) + (dot(xh, cl) + dot(xl, ch))
    raise ValueError(f"unknown precision {precision!r}")


def row_block(n: int, k: int) -> int:
    """Rows per block of the distance matrix: a power of two dividing n."""
    b = 1
    while b * 2 <= n and n % (b * 2) == 0 and b * 2 * k * 4 <= _BLOCK_BYTES:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames="precision")
def assign(x, c, precision: str = "highest"):
    """Nearest centroid of every point and its squared distance."""
    n, d = x.shape
    blk = row_block(n, c.shape[0])
    csq = jnp.sum(c * c, axis=-1)

    def one(xb):
        score = csq[None, :] - 2.0 * cross(xb, c, precision)
        a = jnp.argmin(score, axis=-1).astype(jnp.int32)
        m = jnp.min(score, axis=-1) + jnp.sum(xb * xb, axis=-1)
        return a, m

    a, m = jax.lax.map(one, x.reshape(n // blk, blk, d))
    return a.reshape(n), m.reshape(n)


def cluster_means(x, a, k: int):
    """Scatter-add sums and counts, and the means of non-empty clusters
    (zero where a cluster is empty)."""
    s = jnp.zeros((k, x.shape[1]), jnp.float32).at[a].add(x)
    cnt = jnp.zeros((k,), jnp.float32).at[a].add(1.0)
    return s / jnp.maximum(cnt, 1.0)[:, None], cnt


@functools.partial(jax.jit, static_argnames="precision")
def step(x, c, precision: str = "highest"):
    """``(centroids, assignments, inertia)`` of one Lloyd step from ``c``."""
    a, m = assign(x, c, precision)
    mean, cnt = cluster_means(x, a, c.shape[0])
    return jnp.where((cnt > 0)[:, None], mean, c), a, jnp.sum(m)
