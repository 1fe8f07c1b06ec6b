"""FlashAssign — fused distance + online-argmin assignment (Pallas TPU).

Paper §4.1, adapted for the TPU memory hierarchy:

- grid = (N_tiles, K_tiles) with the K dimension minor-most. On TPU the
  grid is executed sequentially over the minor dimension, so the running
  ``(m, a)`` online-argmin state lives in VMEM scratch and persists across
  the K sweep for a fixed point tile — the Pallas pipeline doubles as the
  paper's double-buffered asynchronous prefetch of centroid tiles.
- the distance cross term ``-2 x.c`` is an MXU matmul per (B_N, B_K) tile
  with f32 accumulation; the per-point constant ``||x||^2`` is dropped
  inside the kernel (it cannot change the argmin) and re-added by the
  wrapper when true distances are requested.
- the full ``N x K`` distance matrix never exists in HBM: per-iteration IO
  is ``O(N d + K d)`` reads + ``O(N)`` writes, vs ``2·Θ(NK)`` for the
  materialized baseline.

The kernel is shape-padded by ``ops.flash_assign``; K-padding is masked
in-kernel with ``+inf`` scores so padded centroids can never win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_INF = float("inf")


def _flash_assign_kernel(x_ref, c_ref, a_ref, m_ref, m_scr, a_scr, *,
                         block_k: int, k_actual: int):
    """One (point-tile, centroid-tile) grid step.

    Scores are laid out transposed, ``(B_K, B_N)``: the min/argmin then
    reduces over sublanes and lands as a lane-dense ``(1, B_N)`` row — the
    layout of the running state and of the output blocks, so nothing is
    relaid into 1-D vectors.
    """
    kt = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kt == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _INF)
        a_scr[...] = jnp.zeros_like(a_scr[...])

    x = x_ref[...]                                   # (bn, d)
    c = c_ref[...]                                   # (bk, d)

    # MXU: cross term with f32 accumulation, transposed (bk, bn).
    cross = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=matmul_precision(x.dtype))
    c32 = c.astype(jnp.float32)
    csq = jnp.sum(c32 * c32, axis=1, keepdims=True)  # (bk, 1)
    score = csq - 2.0 * cross                        # (bk, bn) f32

    # Mask padded centroids (tail tile only).
    k_ids = kt * block_k + jax.lax.broadcasted_iota(jnp.int32, score.shape, 0)
    score = jnp.where(k_ids < k_actual, score, _INF)

    local_m, local_a = argmin_rows(score, kt * block_k)   # (1, bn) each

    # Online argmin: strict '<' keeps the earliest index on exact ties,
    # matching jnp.argmin's first-occurrence semantics.
    run_m = m_scr[...]
    run_a = a_scr[...]
    better = local_m < run_m
    m_scr[...] = jnp.where(better, local_m, run_m)
    a_scr[...] = jnp.where(better, local_a, run_a)

    @pl.when(kt == nk - 1)
    def _flush():
        a_ref[...] = a_scr[...]
        m_ref[...] = m_scr[...]


def matmul_precision(dtype):
    """Full-f32 MXU contraction for f32 operands (the exactness contract:
    scores must not silently drop to one bf16 pass); the operand's own
    precision otherwise."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else None)


def argmin_rows(score: Array, base) -> tuple[Array, Array]:
    """Column-wise ``(min, argmin)`` of a transposed ``(B_K, B_N)`` score
    tile as lane-dense ``(1, B_N)`` rows; ``base`` is the tile's first
    global index. One index-carrying reduction (``jnp.argmin``, first
    occurrence on exact ties) — a min followed by an ``== min`` mask can
    miss when the compiler evaluates the score twice with different
    rounding."""
    m = jnp.min(score, axis=0, keepdims=True)
    a = base + jnp.argmin(score, axis=0, keepdims=True).astype(jnp.int32)
    return m, a


def row_to_col(row: Array) -> Array:
    """A lane-dense ``(1, n)`` row as an ``(n, 1)`` column: one 2-D
    transpose of the row broadcast to a full sublane tile."""
    return jnp.transpose(jnp.broadcast_to(row, (8, row.shape[1])))[:, 0:1]


def flash_assign_raw(x: Array, c: Array, *, block_n: int, block_k: int,
                     k_actual: int, interpret: bool = False
                     ) -> tuple[Array, Array]:
    """Pallas call on pre-padded inputs.

    x: (N_pad, d), c: (K_pad, d) with N_pad % block_n == K_pad % block_k == 0.
    Returns (assignments int32 (N_tiles, 1, block_n), scores f32
    (N_tiles, 1, block_n)) — one lane-dense row per point tile (reshape to
    ``(N_pad,)``) — where score is ``||c_a||^2 - 2 x.c_a`` (add
    ``||x||^2`` for the true squared distance).
    """
    n_pad, d = x.shape
    k_pad = c.shape[0]
    n_tiles = n_pad // block_n
    grid = (n_tiles, k_pad // block_k)

    kernel = functools.partial(
        _flash_assign_kernel, block_k=block_k, k_actual=k_actual)
    row = pl.BlockSpec((None, 1, block_n), lambda i, k: (i, 0, 0))

    return pl.pallas_call(
        kernel,
        name="flash_assign",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, k: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, k: (k, 0)),
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, 1, block_n), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, 1, block_n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_n), jnp.float32),
            pltpu.VMEM((1, block_n), jnp.int32),
        ],
        interpret=interpret,
    )(x, c)
