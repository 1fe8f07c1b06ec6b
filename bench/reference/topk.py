"""Plain brute-force nearest neighbours: the reference a search is
compared with.

``topk`` scores every corpus row against every query (the dense matrix
of ``||x||^2 - 2 q.x``, a block of queries at a time) and keeps the
``k`` smallest with ``lax.top_k``. ``sq_dist`` is the squared distance
of given pairs, taken elementwise (no expanded form), the yardstick for
the distances a search returns. ``precision`` is as in ``lloyd``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.lloyd import cross, row_block


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def topk(q, x, *, k: int, precision: str = "highest"):
    """``(ids int32 (B, k), scores (B, k))`` of the ``k`` nearest corpus rows,
    ascending; a score is the squared distance less ``||q||^2``."""
    b, d = q.shape
    blk = row_block(b, x.shape[0])
    xsq = jnp.sum(x * x, axis=-1)

    def one(qb):
        score = xsq[None, :] - 2.0 * cross(qb, x, precision)
        neg, idx = jax.lax.top_k(-score, k)
        return idx.astype(jnp.int32), -neg

    ids, s = jax.lax.map(one, q.reshape(b // blk, blk, d))
    return ids.reshape(b, k), s.reshape(b, k)


@jax.jit
def sq_dist(q, x, ids):
    """Squared distance of query ``i`` to corpus row ``ids[i, j]``, taken
    elementwise in float32; an id outside the corpus reads +inf."""
    ok = (ids >= 0) & (ids < x.shape[0])
    rows = jnp.take(x, jnp.clip(ids, 0, x.shape[0] - 1), axis=0)
    dist = jnp.sum((rows - q[:, None, :]) ** 2, axis=-1)
    return jnp.where(ok, dist, jnp.inf)
