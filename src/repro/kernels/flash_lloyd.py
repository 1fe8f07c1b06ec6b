"""FlashLloyd — fused assignment + centroid statistics (Pallas TPU).

One Lloyd iteration's sufficient statistics in a single IO-optimal pass.
The two-pass pipeline (FlashAssign, then sort-inverse update) streams the
point set from HBM three times per iteration: the assignment kernel reads
``x``, the ``argsort``/row-gather prologue reads and rewrites it as
``x_sorted``, and the update kernel reads ``x_sorted`` again. FlashLloyd
exploits that once a point tile's argmin is known the tile is *already
resident in VMEM* — its contribution ``onehot^T @ x_tile`` to the centroid
sums can be accumulated immediately, so the whole iteration needs exactly
one ``O(N d)`` read (see DESIGN.md for the traffic model of all three
dataflows).

Structure: grid ``(N_tiles,)`` with an inner ``fori_loop`` K-sweep.

- sweep 1 replays the FlashAssign online argmin over ``K_pad/B_K``
  centroid slices of the VMEM-resident centroid block (the ``||x||^2``
  term is dropped on-chip, re-added for the inertia only);
- sweep 2 revisits the same centroid slices, builds the tile-local
  one-hot ``(B_K, B_N)`` in registers, and accumulates ``onehot @ x_tile``
  plus counts into the ``(K_pad, d)`` sums and
  ``(K_pad/B_K, B_K)`` count rows, f32 output blocks that stay resident
  in VMEM for the whole grid
  (constant index map — initialized at tile 0, flushed once at the end).

For float32 points the statistics product takes three bf16 MXU passes
where ``Precision.HIGHEST`` takes six: the one-hot is exact in bf16, so
only ``x`` needs splitting, and ``split_bf16x3`` splits it exactly, once
per tile (DESIGN.md §3). The distance product of sweep 1 stays at
HIGHEST.

The price is that the full centroid set and the f32 accumulators must be
VMEM-resident: ``~2 K_pad·d·4`` bytes. ``core.heuristics.fused_footprint``
models this and auto-falls back to the two-pass path when it exceeds the
VMEM budget — which is why both dataflows survive (sort-inverse remains
the large-K path).

Shape padding is done by ``ops.flash_lloyd_step``; padded centroids are
masked with ``+inf`` scores (can never win), padded points are masked out
of the one-hot, the counts, and the inertia via ``n_actual``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_assign import (argmin_rows, matmul_precision,
                                        row_to_col)

Array = jax.Array

_INF = float("inf")


def split_bf16x3(x: Array) -> tuple[Array, Array, Array]:
    """``x`` (float32) as three bfloat16 terms ``hi + mid + lo == x``,
    exactly: each remainder is taken in float32, where it is exact, and
    8 + 8 + 8 significand bits cover float32's 24. Products of an operand
    that bfloat16 holds exactly (a one-hot) with each term are exact, so
    three bf16 MXU passes give what ``Precision.HIGHEST``'s six give."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _flash_lloyd_kernel(x_ref, c_ref, a_ref, s_ref, cnt_ref, j_ref, *,
                        block_n: int, block_k: int, k_actual: int,
                        n_actual: int):
    """One point-tile grid step: argmin K-sweep, then accumulate K-sweep.

    Sweep 1 scores transposed ``(B_K, B_N)`` slices (FlashAssign's
    layout), so the argmin state and the assignment row are lane-dense
    ``(1, B_N)`` rows. Sweep 2 compares that row with the slice's
    centroid ids down the sublanes: the ``(B_K, B_N)`` one-hot is the
    MXU's plain left operand, with no transpose, and a ones row times its
    transpose gives the lane-dense ``(1, B_K)`` count row.
    """
    i = pl.program_id(0)
    nk = c_ref.shape[0] // block_k

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref[...])
        cnt_ref[...] = jnp.zeros_like(cnt_ref[...])
        j_ref[0, 0] = jnp.float32(0.0)

    x = x_ref[...]                                    # (bn, d), resident
    prec = matmul_precision(x.dtype)
    # rank-2 iota: Mosaic rejects 1-D iota
    row_ids = i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, 1), 0)
    row_valid = row_ids < n_actual                    # (bn, 1)

    # ---- sweep 1: online argmin over centroid slices (FlashAssign math).
    def _argmin_body(kt, carry):
        m, a = carry
        start = pl.multiple_of(kt * block_k, block_k)
        c = c_ref[pl.ds(start, block_k), :]           # (bk, d)
        cross = jax.lax.dot_general(                  # (bk, bn)
            c, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        c32 = c.astype(jnp.float32)
        csq = jnp.sum(c32 * c32, axis=1, keepdims=True)
        score = csq - 2.0 * cross                     # (bk, bn) f32
        k_ids = kt * block_k + jax.lax.broadcasted_iota(
            jnp.int32, score.shape, 0)
        score = jnp.where(k_ids < k_actual, score, _INF)
        local_m, local_a = argmin_rows(score, kt * block_k)
        # strict '<' keeps the earliest index on exact ties (argmin parity)
        better = local_m < m
        return jnp.where(better, local_m, m), jnp.where(better, local_a, a)

    m, a = jax.lax.fori_loop(
        0, nk, _argmin_body,
        (jnp.full((1, block_n), _INF, jnp.float32),
         jnp.zeros((1, block_n), jnp.int32)))
    a_ref[...] = a

    # Inertia: re-add the dropped ||x||^2, clamp fp residue, mask padding.
    m_col = row_to_col(m)                             # (bn, 1)
    x32 = x.astype(jnp.float32)
    xsq = jnp.sum(x32 * x32, axis=-1, keepdims=True)
    dist = jnp.maximum(m_col + xsq, 0.0)
    j_ref[0, 0] += jnp.sum(jnp.where(row_valid, dist, 0.0))

    # ---- sweep 2: one-hot statistics into the resident accumulators.
    # float32 x: its exact bf16 terms, split once per tile, smallest first
    # (one bf16 pass each, against HIGHEST's six for the whole product).
    if x.dtype == jnp.float32:
        terms = split_bf16x3(x)[::-1]
    else:
        terms = (x,)
    oh_dtype = terms[0].dtype
    col_ids = i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1)
    a_valid = jnp.where(col_ids < n_actual, a, -1)    # padding matches none
    ones = jnp.ones((8, block_n), oh_dtype)           # one sublane tile

    def _accum_body(kt, _):
        start = pl.multiple_of(kt * block_k, block_k)
        k_ids = kt * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_n), 0)
        oh = (a_valid == k_ids).astype(oh_dtype)     # (bk, bn), exact
        # MXU: (bk, bn) @ (bn, d) f32-accumulated == slice-local sums.
        partial = None
        for t in terms:
            p = jnp.dot(oh, t, preferred_element_type=jnp.float32)
            partial = p if partial is None else partial + p
        s_ref[pl.ds(start, block_k), :] += partial
        cnt = jax.lax.dot_general(                    # (8, bk), exact
            ones, oh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        cnt_ref[pl.ds(kt, 1), :] += cnt[0:1, :]
        return 0

    jax.lax.fori_loop(0, nk, _accum_body, 0)


def flash_lloyd_raw(x: Array, c: Array, *, block_n: int, block_k: int,
                    k_actual: int, n_actual: int, interpret: bool = False
                    ) -> tuple[Array, Array, Array, Array]:
    """Pallas call on pre-padded inputs.

    x: (N_pad, d), c: (K_pad, d) with N_pad % block_n == K_pad % block_k == 0.
    Returns ``(assignments int32 (N_tiles, 1, block_n), sums f32
    (K_pad, d), counts f32 (K_pad / block_k, block_k), inertia f32
    (1, 1))`` — assignments and counts in lane-dense rows (reshape to
    ``(N_pad,)`` / ``(K_pad,)``); padded rows/centroids contribute
    nothing to the statistics.
    """
    n_pad, d = x.shape
    k_pad = c.shape[0]
    n_tiles = n_pad // block_n
    nk = k_pad // block_k

    kernel = functools.partial(
        _flash_lloyd_kernel, block_n=block_n, block_k=block_k,
        k_actual=k_actual, n_actual=n_actual)

    return pl.pallas_call(
        kernel,
        name="flash_lloyd_step",
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k_pad, d), lambda i: (0, 0)),   # resident
        ],
        out_specs=[
            pl.BlockSpec((None, 1, block_n), lambda i: (i, 0, 0)),
            pl.BlockSpec((k_pad, d), lambda i: (0, 0)),   # resident acc
            pl.BlockSpec((nk, block_k), lambda i: (0, 0)),
            # scalar inertia accumulator lives in SMEM (Mosaic idiom)
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, 1, block_n), jnp.int32),
            jax.ShapeDtypeStruct((k_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((nk, block_k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, c)
