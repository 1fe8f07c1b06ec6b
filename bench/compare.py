"""The numbers that decide ``correct``, each computed from what the timed
path produced and the plain reference (``bench.reference``).

A fit's first step, from initial centroids ``c0`` drawn from the seed, is
judged by:

- ``assign_gap``: the widest amount by which a point's assigned centroid
  lies farther from it than the reference's nearest one, both distances
  taken elementwise, over ``||x||^2 + ||c||^2`` (0 where the program
  picks the nearest; a near-tie rounds to a few float32 ulps).
- ``update_gap``: the widest distance between a returned centroid and
  the mean of the points the step assigned to it (non-empty clusters),
  over the largest such mean's magnitude. It holds the statistics and
  the centroid update to the step's own assignments.
- ``inertia_gap``: the relative gap between the step's inertia and the
  reference's.
- ``assign_mismatch``: the share of points assigned otherwise than by
  the reference (near-ties included).

A search is judged over a sample of served queries by:

- ``miss_share``: the share of served result slots that are not among
  the exact nearest neighbours, up to near-ties: an id that is invalid,
  repeated in its row, or farther than the reference's k-th neighbour
  by more than ``TIE_ULPS`` float32 ulps of ``||q||^2 + ||x||^2``.
- ``dist_gap``: the widest gap between a returned distance and the
  elementwise squared distance of the returned id, over
  ``||q||^2 + ||x||^2``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import lloyd as ref_lloyd
from bench.reference import topk as ref_topk

TIE_ULPS = 8
_EPS = float(np.finfo(np.float32).eps)


@jax.jit
def _update_gap(x, a, c):
    mean, cnt = ref_lloyd.cluster_means(x, a, c.shape[0])
    live = (cnt > 0)[:, None]
    gap = jnp.max(jnp.where(live, jnp.abs(c - mean), 0.0))
    return gap / jnp.max(jnp.where(live, jnp.abs(mean), 0.0))


def _blocks(n: int) -> int:
    b = n
    while b > (1 << 18) and b % 2 == 0:
        b //= 2
    return b


@jax.jit
def _assign_gap(x, c0, a, a_ref):
    n, d = x.shape
    k = c0.shape[0]
    blk = _blocks(n)

    def one(args):
        xb, ab, rb = args
        ok = (ab >= 0) & (ab < k)
        got = jnp.sum((xb - c0[jnp.clip(ab, 0, k - 1)]) ** 2, axis=-1)
        cr = c0[rb]
        best = jnp.sum((xb - cr) ** 2, axis=-1)
        scale = jnp.sum(xb * xb, axis=-1) + jnp.sum(cr * cr, axis=-1)
        return jnp.max(jnp.where(ok, (got - best) / scale, jnp.inf))

    return jnp.max(jax.lax.map(one, (x.reshape(n // blk, blk, d),
                                     a.reshape(n // blk, blk),
                                     a_ref.reshape(n // blk, blk))))


def step_numbers(x, c0, step, ref) -> dict[str, float]:
    """``step``: the program's ``(centroids, assignments, inertia)`` from
    ``c0``; ``ref``: the reference's ``(assignments, distances)``."""
    c, a, j = step
    a_r, m_r = ref
    j, j_r = float(j), float(jnp.sum(m_r))
    return {
        "assign_gap": float(_assign_gap(x, c0, a, a_r)),
        "update_gap": float(_update_gap(x, a, c)),
        "inertia_gap": abs(j - j_r) / j_r if np.isfinite(j) else float("inf"),
        "assign_mismatch": float(jnp.mean(a != a_r)),
    }


def search_numbers(x, q, ids, dists, k: int) -> dict[str, float]:
    """``q`` (B, d) queries, ``ids``/``dists`` (B, k) what was served."""
    ids = jnp.asarray(ids, jnp.int32)
    dists = jnp.asarray(dists, jnp.float32)
    ref_ids, _ = ref_topk.topk(q, x, k=k)
    d_ref = ref_topk.sq_dist(q, x, ref_ids)
    d_got = ref_topk.sq_dist(q, x, ids)
    kth = jnp.max(d_ref, axis=1, keepdims=True)
    qsq = jnp.sum(q * q, axis=-1, keepdims=True)
    xsq = jnp.sum(x * x, axis=-1)
    row_sq = jnp.take(xsq, jnp.clip(ids, 0, x.shape[0] - 1))
    kth_sq = jnp.max(jnp.take(xsq, ref_ids), axis=1, keepdims=True)
    tol = TIE_ULPS * _EPS * (qsq + kth_sq)
    far = ~(d_got <= kth + tol)              # an invalid id reads +inf
    order = jnp.argsort(ids, axis=1)
    srt = jnp.take_along_axis(ids, order, axis=1)
    dup = jnp.concatenate([jnp.zeros_like(srt[:, :1], bool),
                           srt[:, 1:] == srt[:, :-1]], axis=1)
    miss = jnp.sum(jnp.take_along_axis(far, order, axis=1) | dup)
    scale = qsq + row_sq
    gap = jnp.where(jnp.isfinite(d_got), jnp.abs(dists - d_got) / scale,
                    jnp.inf)
    return {"miss_share": float(miss) / ids.size,
            "dist_gap": float(jnp.max(gap))}


def judge(numbers: dict[str, float], limits: dict[str, float]
          ) -> tuple[bool, list[dict]]:
    """Every number that has a limit against it; a limit with no number,
    or a non-finite number, fails."""
    rows, ok = [], True
    for name, lim in sorted(limits.items()):
        v = numbers.get(name)
        ok &= v is not None and bool(np.isfinite(v)) and v <= lim
        rows.append({"name": name, "value": v, "limit": lim})
    return ok, rows
