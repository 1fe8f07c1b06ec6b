"""Sort-Inverse Update — contention-free centroid aggregation (Pallas TPU).

Paper §4.2 adapted to TPU. The GPU version sorts the assignment vector and
replaces per-token atomic scatters with per-segment merges. TPU has no
per-word atomics (XLA scatter serializes on duplicate indices — the same
pathology), so we re-derive the insight as a *block-sparse one-hot matmul*:

1. ``sorted_idx = argsort(a)`` (1-D, 4-byte keys — O(N log N) ≪ O(Nd)).
2. One streaming XLA row-gather materializes ``X_sorted`` (O(Nd), HBM-bw
   bound; see DESIGN.md for why this beats in-kernel row gathers on TPU).
3. Because ids are now sorted, each point tile of ``B_N`` rows only spans a
   *contiguous* range of centroid tiles. The host-side (XLA) prologue
   computes the exact list of intersecting (n_tile, k_tile) pairs — at most
   ``ceil(N/B_N) + ceil(K/B_K)`` of them — sorts the list by k_tile, and
   feeds it to the kernel via **scalar prefetch** so the Pallas pipeline
   only DMAs and computes the intersecting tiles.
4. Each grid step builds the tile-local one-hot (B_N, B_K) in registers and
   issues one MXU matmul ``onehot^T @ x_tile`` accumulated into the output
   block, which stays resident in VMEM for the whole run of a k_tile
   (consecutive revisits). The single flush per k-run is the TPU analogue
   of the paper's "one atomic merge per segment".

FLOPs: O(N·B_K·d) instead of O(N·K·d) dense; write-path: exactly
``K_pad + B_K`` output rows, zero scatters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_assign import matmul_precision, row_to_col

Array = jax.Array


def build_tile_pairs(a_sorted: Array, *, block_n: int, block_k: int,
                     n_tiles: int, k_tiles: int) -> tuple[Array, Array]:
    """Compute the compacted (n_tile, k_tile) intersection list.

    ``a_sorted`` is the padded, sorted assignment vector (padding id ==
    k_tiles * block_k so padded points land in the dummy k-tile). Returns
    (pair_n, pair_k), both int32 of static length ``n_tiles + k_tiles + 1``,
    sorted by (k_tile, n_tile); unused entries have k == k_tiles (a dummy
    output block that is sliced off by the wrapper).
    """
    g_max = n_tiles + k_tiles + 1
    ids2d = a_sorted.reshape(n_tiles, block_n)
    lo = ids2d[:, 0] // block_k                      # (nN,) first k-tile
    hi = ids2d[:, -1] // block_k                     # (nN,) last k-tile
    cnt = hi - lo + 1
    off = jnp.concatenate([jnp.zeros((1,), cnt.dtype), jnp.cumsum(cnt)])
    total = off[-1]

    g = jnp.arange(g_max, dtype=jnp.int32)
    # n such that off[n] <= g < off[n+1]
    n_of_g = jnp.searchsorted(off[1:], g, side="right").astype(jnp.int32)
    valid = g < total
    n_idx = jnp.clip(n_of_g, 0, n_tiles - 1)
    k_idx = jnp.where(valid, lo[n_idx].astype(jnp.int32)
                      + (g - off[n_idx].astype(jnp.int32)),
                      jnp.int32(k_tiles))
    n_idx = jnp.where(valid, n_idx, 0)
    # Sort by (k, n) so output-block revisits are consecutive. Dummy
    # entries (k == k_tiles) sort to the end.
    # int32 is safe: k_tiles*(n_tiles+1) < 2^31 for any shape we can lower.
    order = jnp.argsort(k_idx * jnp.int32(n_tiles + 1) + n_idx)
    return n_idx[order].astype(jnp.int32), k_idx[order].astype(jnp.int32)


def _sort_inverse_kernel(pair_n_ref, pair_k_ref, a_ref, x_ref,
                         s_ref, cnt_ref, *, block_k: int):
    g = pl.program_id(0)
    k_idx = pair_k_ref[g]
    prev_k = pair_k_ref[jnp.maximum(g - 1, 0)]
    first = jnp.logical_or(g == 0, prev_k != k_idx)

    ids = row_to_col(a_ref[...])                      # (bn, 1) int32, sorted
    x = x_ref[...]                                    # (bn, d)

    # Tile-local one-hot relative to this k-tile's base id. Out-of-range
    # ids (rows belonging to neighbouring k-tiles) produce all-zero rows.
    rel = ids - k_idx * block_k                       # (bn, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], block_k), 1)
    onehot = (rel == cols).astype(x.dtype)            # (bn, bk)

    # MXU: (bk, bn) @ (bn, d) with f32 accumulation == segment-local sums.
    partial = jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=matmul_precision(x.dtype))
    # (1, bk) lane-dense row
    pcnt = jnp.sum(onehot.astype(jnp.float32), axis=0, keepdims=True)

    @pl.when(first)
    def _store():
        s_ref[...] = partial
        cnt_ref[...] = pcnt

    @pl.when(jnp.logical_not(first))
    def _accum():
        s_ref[...] += partial
        cnt_ref[...] += pcnt


def sort_inverse_update_raw(x_sorted: Array, a_sorted: Array,
                            pair_n: Array, pair_k: Array, *,
                            block_n: int, block_k: int, k_tiles: int,
                            interpret: bool = False) -> tuple[Array, Array]:
    """Pallas call on pre-sorted, pre-padded inputs.

    Returns ``(sums f32 ((k_tiles+1)*block_k, d), counts f32
    (1, (k_tiles+1)*block_k))`` — the trailing dummy block collects
    padding and is sliced off by ops.
    """
    n_pad, d = x_sorted.shape
    g_max = pair_n.shape[0]
    k_rows = (k_tiles + 1) * block_k
    # assignments as lane-dense rows, one per point tile
    a_rows = a_sorted.reshape(n_pad // block_n, 1, block_n)

    kernel = functools.partial(_sort_inverse_kernel, block_k=block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g_max,),
        in_specs=[
            pl.BlockSpec((None, 1, block_n),
                         lambda g, pn, pk: (pn[g], 0, 0)),
            pl.BlockSpec((block_n, d), lambda g, pn, pk: (pn[g], 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_k, d), lambda g, pn, pk: (pk[g], 0)),
            pl.BlockSpec((1, block_k), lambda g, pn, pk: (0, pk[g])),
        ],
    )

    return pl.pallas_call(
        kernel,
        name="sort_inverse_update",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((k_rows, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k_rows), jnp.float32),
        ],
        interpret=interpret,
    )(pair_n, pair_k, a_rows, x_sorted)
