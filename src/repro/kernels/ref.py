"""Pure-jnp reference oracles for the flash-kmeans kernels.

These implement the *standard* (paper Algorithm 1) dataflow faithfully:

- ``assign_ref``     materializes the full ``N x K`` distance matrix in
  memory and then reduces it row-wise (Kernel 1 + Kernel 2 of Alg. 1).
- ``update_scatter_ref`` performs token-granularity scatter-adds
  (Kernel 3 + 4 of Alg. 1) — on TPU this lowers to an XLA scatter, the
  moral equivalent of the GPU atomic-contention path.
- ``update_dense_onehot_ref`` is the contention-free-but-FLOP-dense
  alternative (``S = A_onehot^T X``) used as a second baseline.

They double as numerical oracles for the Pallas kernels in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def pairwise_sq_dists(x: Array, c: Array) -> Array:
    """Materialized ``N x K`` squared-distance matrix (f32).

    Uses the expanded form ``||x||^2 + ||c||^2 - 2 x.c`` like every GPU
    library does (maps onto a matmul).
    """
    x32 = x.astype(jnp.float32)
    c32 = c.astype(jnp.float32)
    xsq = jnp.sum(x32 * x32, axis=-1, keepdims=True)            # (N, 1)
    csq = jnp.sum(c32 * c32, axis=-1)                            # (K,)
    cross = jax.lax.dot_general(
        x, c, (((x.ndim - 1,), (c.ndim - 1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                            # (N, K)
    return xsq + csq[None, :] - 2.0 * cross


def assign_ref(x: Array, c: Array) -> tuple[Array, Array]:
    """Standard assignment: materialize D, then row-wise argmin.

    Returns ``(assignments int32 (N,), min_sq_dist f32 (N,))``.
    """
    d = pairwise_sq_dists(x, c)
    a = jnp.argmin(d, axis=-1).astype(jnp.int32)
    m = jnp.min(d, axis=-1)
    return a, m


def assign_ref_crossterm(x: Array, c: Array) -> tuple[Array, Array]:
    """Assignment using the x-norm-free score ``||c||^2 - 2 x.c``.

    The per-row constant ``||x||^2`` does not change the argmin; the flash
    kernel uses this form on-chip, so tests compare against it for
    bitwise-comparable scores. Returned min value excludes ``||x||^2``.
    """
    c32 = c.astype(jnp.float32)
    csq = jnp.sum(c32 * c32, axis=-1)
    cross = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    score = csq[None, :] - 2.0 * cross
    a = jnp.argmin(score, axis=-1).astype(jnp.int32)
    m = jnp.min(score, axis=-1)
    return a, m


def probe_ref(q: Array, c: Array, l: int, *, want_dists: bool = True
              ) -> tuple[Array, Array]:
    """Dense top-L oracle for the FlashProbe kernel.

    Materializes the full score matrix in the kernel's own form
    (``||c||^2 - 2 q.c``, per-query constant dropped) and reduces it with
    ``jax.lax.top_k`` — so ``want_dists=False`` values are bitwise
    comparable with the fused kernel and ties break identically (lower
    index first). Returns ``(indices int32 (N, l), values f32 (N, l))``
    ascending; with ``want_dists=True`` the per-query ``||q||^2`` is
    re-added like ``ops.flash_probe`` (bitwise parity ends here: the
    re-add happens in two different XLA graphs).
    """
    c32 = c.astype(jnp.float32)
    csq = jnp.sum(c32 * c32, axis=-1)
    cross = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    score = csq[None, :] - 2.0 * cross
    neg_v, idx = jax.lax.top_k(-score, l)
    if not want_dists:
        return idx.astype(jnp.int32), -neg_v
    q32 = q.astype(jnp.float32)
    d = -neg_v + jnp.sum(q32 * q32, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), jnp.maximum(d, 0.0)


def update_scatter_ref(x: Array, a: Array, k: int) -> tuple[Array, Array]:
    """Scatter-style centroid statistics (the contention-prone baseline).

    Returns ``(sums f32 (K, d), counts f32 (K,))``.
    """
    n, d = x.shape
    s = jnp.zeros((k, d), jnp.float32).at[a].add(x.astype(jnp.float32))
    cnt = jnp.zeros((k,), jnp.float32).at[a].add(1.0)
    return s, cnt


def update_dense_onehot_ref(x: Array, a: Array, k: int) -> tuple[Array, Array]:
    """Dense one-hot matmul statistics: ``S = A^T X`` — contention-free but
    O(NKd) FLOPs (the MXU-friendly strawman the sort-inverse kernel beats)."""
    onehot = (a[:, None] == jnp.arange(k, dtype=a.dtype)[None, :])
    oh = onehot.astype(jnp.float32)
    s = jax.lax.dot_general(
        oh, x.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    cnt = jnp.sum(oh, axis=0)
    return s, cnt


def lloyd_stats_ref(x: Array, c: Array) -> tuple[Array, Array, Array, Array]:
    """Oracle for the fused FlashLloyd pass: standard assignment composed
    with dense one-hot statistics.

    Returns ``(assignments int32 (N,), sums f32 (K, d), counts f32 (K,),
    inertia f32 ())`` — the exact quantities ``ops.flash_lloyd_step``
    produces in a single kernel.
    """
    a, m = assign_ref(x, c)
    s, cnt = update_dense_onehot_ref(x, a, c.shape[0])
    return a, s, cnt, jnp.sum(m)


def centroid_update_ref(x: Array, a: Array, c_prev: Array) -> Array:
    """Full reference centroid update with empty-cluster fallback."""
    k = c_prev.shape[0]
    s, cnt = update_scatter_ref(x, a, k)
    new_c = s / jnp.maximum(cnt, 1.0)[:, None]
    return jnp.where((cnt > 0)[:, None], new_c, c_prev.astype(jnp.float32)).astype(c_prev.dtype)
