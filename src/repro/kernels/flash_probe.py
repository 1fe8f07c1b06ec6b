"""FlashProbe — fused distance + online top-L (Pallas TPU).

FlashAssign generalized from the online *argmin* to an online *L-best*
selection: the IVF search primitive. The index subsystem calls it in
three forms, all sharing one selection routine:

- **nprobe centroid selection** — queries against the (K, d) coarse
  centroid set, L = nprobe;
- **list-major posting-list scan** (``flash_scan_lists``) — a group of
  queries that probe the same list against that list's rows, streamed
  in place from the store through scalar-prefetched block indices,
  L = topk (the flat fp32 search);
- **gathered posting-list scan** — the grouped variants: query tiles,
  each query scored against its own gathered (nprobe·cap, d) candidate
  block, L = topk (routed, quantized, rescore and sharded searches).

Structure mirrors FlashAssign: grid ``(Q_tiles, K_tiles)`` with K
minor-most, so the running ``(vals, idxs)`` L-best state lives in VMEM
scratch and persists across the K sweep for a fixed query tile. The
``N x K`` score matrix never exists in HBM — per-sweep IO is
``O(Q d + K d)`` reads + ``O(Q L)`` writes.

Per grid step the tile's ``(B_Q, B_K)`` crossterm scores and the running
L-best pool are reduced together by L rounds of (min, lowest-index
argmin, mask) in a ``fori_loop`` — one round's temporaries live at a
time, which keeps the kernel inside the scoped-VMEM limit at the
planner's tiles. Tie-breaking matches ``jax.lax.top_k``: for equal
scores the lower centroid index wins, because each round picks the
lowest global index among the entries equal to the row minimum, and

- K tiles are swept in ascending index order, so every pool entry has a
  lower index than every entry of the new tile;
- the running pool itself is kept sorted by (score, index) — the
  invariant each selection round preserves.

The kernel keeps the x-norm-free score ``||c||^2 - 2 q.c`` (the per-query
constant ``||q||^2`` cannot change the selection); the wrapper re-adds it
when true squared distances are requested. K-padding is masked in-kernel
with ``+inf`` so padded centroids can never be selected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_assign import matmul_precision

Array = jax.Array

_INF = float("inf")


def _select_l_best(pool_v: Array, pool_i: Array, tile_v: Array,
                   tile_i: Array, l: int) -> tuple[Array, Array]:
    """L rounds of (min, lowest-index argmin, mask) over the running pool
    ``(B_Q, L)`` and the new tile ``(B_Q, B)``, kept as two arrays (no
    unaligned lane concatenation).

    Returns the L smallest scores per row in ascending (score, index)
    order. Each round takes the row minimum and, among entries equal to
    it, the lowest global index — ``jax.lax.top_k``'s tie rule: the pool
    holds earlier (lower-index) tiles' winners, and within a tile
    indices ascend with position. The rounds run in a ``fori_loop`` so
    only one round's temporaries are ever live.
    """
    big = jnp.iinfo(jnp.int32).max
    out_cols = jax.lax.broadcasted_iota(jnp.int32, pool_v.shape, 1)

    def rnd(j, carry):
        pv, tv, ov, oi = carry
        m = jnp.minimum(jnp.min(pv, axis=1, keepdims=True),
                        jnp.min(tv, axis=1, keepdims=True))
        idx = jnp.minimum(
            jnp.min(jnp.where(pv == m, pool_i, big), axis=1, keepdims=True),
            jnp.min(jnp.where(tv == m, tile_i, big), axis=1, keepdims=True))
        ov = jnp.where(out_cols == j, m, ov)
        oi = jnp.where(out_cols == j, idx, oi)
        pv = jnp.where(pool_i == idx, _INF, pv)
        tv = jnp.where(tile_i == idx, _INF, tv)
        return pv, tv, ov, oi

    _, _, ov, oi = jax.lax.fori_loop(
        0, l, rnd, (pool_v, tile_v, jnp.full_like(pool_v, _INF),
                    jnp.zeros_like(pool_i)))
    return ov, oi


def _flash_probe_kernel(q_ref, c_ref, csq_ref, i_ref, v_ref, v_scr, i_scr, *,
                        block_k: int, k_actual: int, l: int):
    """One (query-tile, centroid-tile) grid step.

    ``csq_ref`` is the (1, B_K) strip of precomputed ``||c||^2`` — the
    caller computes it once per centroid set (and the index layer caches
    it across searches) instead of re-reducing ``c*c`` on every grid
    step of every query batch.
    """
    kt = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kt == 0)
    def _init():
        v_scr[...] = jnp.full_like(v_scr[...], _INF)
        i_scr[...] = jnp.zeros_like(i_scr[...])

    q = q_ref[...]                                   # (bq, d)
    c = c_ref[...]                                   # (bk, d)

    # MXU: cross term with f32 accumulation (FlashAssign math).
    cross = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=matmul_precision(q.dtype))
    score = csq_ref[...] - 2.0 * cross               # (bq, bk) f32

    # Mask padded centroids (tail tile only).
    k_ids = kt * block_k + jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    score = jnp.where(k_ids < k_actual, score, _INF)

    # Merge the running L-best (earlier tiles = lower indices) with this
    # tile's candidates — lowest-index tie-breaking gives top_k ties.
    new_v, new_i = _select_l_best(v_scr[...], i_scr[...], score, k_ids, l)
    v_scr[...] = new_v
    i_scr[...] = new_i

    @pl.when(kt == nk - 1)
    def _flush():
        i_ref[...] = i_scr[...]
        v_ref[...] = v_scr[...]


def _flash_probe_grouped_kernel(q_ref, c_ref, i_ref, v_ref, v_scr, i_scr, *,
                                block_c: int, c_actual: int, l: int):
    """One (query-tile, candidate-tile) grid step, per-query candidates.

    Unlike the shared-centroid kernel, each query row scores its *own*
    candidate slice (``c_ref`` carries a leading query axis), so the
    cross term is a VPU mul-reduce over d instead of an MXU matmul —
    the honest dataflow of an IVF posting-list scan, where no two
    queries share a candidate set. Selection state and tie-breaking are
    identical to the shared kernel.
    """
    ct = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ct == 0)
    def _init():
        v_scr[...] = jnp.full_like(v_scr[...], _INF)
        i_scr[...] = jnp.zeros_like(i_scr[...])

    q = q_ref[...].astype(jnp.float32)               # (bq, d)
    c = c_ref[...].astype(jnp.float32)               # (bq, bc, d)

    cross = jnp.sum(q[:, None, :] * c, axis=-1)      # (bq, bc) f32
    csq = jnp.sum(c * c, axis=-1)                    # (bq, bc) f32
    score = csq - 2.0 * cross

    c_ids = ct * block_c + jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    score = jnp.where(c_ids < c_actual, score, _INF)

    new_v, new_i = _select_l_best(v_scr[...], i_scr[...], score, c_ids, l)
    v_scr[...] = new_v
    i_scr[...] = new_i

    @pl.when(ct == nc - 1)
    def _flush():
        i_ref[...] = i_scr[...]
        v_ref[...] = v_scr[...]


def _flash_probe_grouped_q8_kernel(q_ref, c_ref, s_ref, i_ref, v_ref,
                                   v_scr, i_scr, *, block_w: int,
                                   w_total: int, l: int):
    """One (query-tile, probe-slot, code-tile) grid step.

    The quantized posting-list scan: candidates arrive as int8 residual
    codes plus a per-slot f32 scale, laid out ``(B, nprobe, W, d)`` —
    probe-rank major, exactly the fp32 scan's candidate order. The
    query side is pre-shifted per probe slot (``q' = q - anchor[cell]``,
    computed once per (query, probe) outside the kernel, ``O(b·nprobe·d)``
    HBM — never per candidate), so the in-kernel score is the *true*
    quantized squared distance

        ||q' - s·code||^2 = ||q'||^2 - 2 s (q'.code) + s^2 ||code||^2

    which is globally comparable across probe slots (no per-cell offset
    to reconcile). Dequantization happens in VMEM against the resident
    tile: HBM streams 1 byte/dim + one f32 scale per row instead of 4
    bytes/dim. Empty / padded slots carry scale exactly 0.0 and are
    masked to +inf — no id lookup in the hot loop. Selection state and
    tie rules are the grouped fp32 kernel's.
    """
    pt = pl.program_id(1)
    wt = pl.program_id(2)
    np_ = pl.num_programs(1)
    nw = pl.num_programs(2)

    @pl.when((pt == 0) & (wt == 0))
    def _init():
        v_scr[...] = jnp.full_like(v_scr[...], _INF)
        i_scr[...] = jnp.zeros_like(i_scr[...])

    qp = q_ref[...].astype(jnp.float32)                  # (bq, d)
    c = c_ref[...].astype(jnp.float32)                   # (bq, bw, d)
    s = s_ref[...]                                       # (bq, bw) f32

    # ||q' - s c||^2 with the scale factored out of the d-reductions, so
    # the (bq, bw) scale row never has to be broadcast along d
    cross = jnp.sum(qp[:, None, :] * c, axis=-1)          # (bq, bw)
    csq = jnp.sum(c * c, axis=-1)
    qsq = jnp.sum(qp * qp, axis=-1, keepdims=True)
    score = qsq - 2.0 * s * cross + (s * s) * csq

    c_ids = (pt * w_total + wt * block_w
             + jax.lax.broadcasted_iota(jnp.int32, score.shape, 1))
    score = jnp.where(s > 0.0, score, _INF)

    new_v, new_i = _select_l_best(v_scr[...], i_scr[...], score, c_ids, l)
    v_scr[...] = new_v
    i_scr[...] = new_i

    @pl.when((pt == np_ - 1) & (wt == nw - 1))
    def _flush():
        i_ref[...] = i_scr[...]
        v_ref[...] = v_scr[...]


def _flash_scan_lists_kernel(cnt_ref, base_ref, nxt_ref, blk_ref, q_ref,
                             c_hbm, i_ref, v_ref, buf, sem, *, block_w: int,
                             n_w: int, l: int, paged: bool, mxu: bool):
    """One segment of the list-major scan: one grid step.

    A segment is up to ``G`` queries that probe the same posting list.
    The step walks the list's ``ceil(cnt/B_W)`` tiles in slot order, each
    copied once from the store (``c_hbm``, left in HBM) for the whole
    group, so a padding segment (``cnt`` 0) reads and computes nothing.
    Copies are double-buffered through ``buf`` across tiles *and*
    segments: the last tile of a segment starts the copy of the first
    tile of the next segment that has rows (``nxt_ref``), into the slot
    that segment's running tile count (``base_ref``) gives.

    Where tile ``t`` lives: ``paged``, it is the whole page
    ``c_hbm[blk[s·W + t]]``; otherwise the list is contiguous rows of
    ``c_hbm[blk[s]]`` and the tile is rows ``[t·B_W, (t+1)·B_W)``, drawn
    back to end at the last row where they would run past it (its
    leading rows, already scanned, are masked). Rows past ``cnt`` are
    masked to ``+inf``.

    On the chip (``mxu``) the cross term is one ``(G, d)·(d, B_W)`` MXU
    product at full f32 precision and ``||c||^2`` is reduced once per
    tile from the same transposed tile. The interpreter computes both as
    per-element f32 multiply-reduces over ``d``, the grouped scan's own
    arithmetic: XLA's CPU dot rounds differently with the tile width,
    this form does not, so CPU results are independent of ``B_W``
    (padded tiles and pages agree) and equal the gathered scan's bit for
    bit. Selection and tie rules are the shared kernel's: within a
    segment the lower slot wins.
    """
    s = pl.program_id(0)
    n_seg = pl.num_programs(0)
    rows = c_hbm.shape[1]
    n = cnt_ref[s]
    nt = (n + block_w - 1) // block_w
    base = base_ref[s]
    nxt = nxt_ref[s]

    def source(seg, t):
        """(payload block, first row copied, rows of it already seen)."""
        if paged:
            return blk_ref[seg * n_w + t], 0, 0
        start = pl.multiple_of(jnp.minimum(t * block_w, rows - block_w), 8)
        return blk_ref[seg], start, t * block_w - start

    def copy(seg, t, slot):
        blk, start, _ = source(seg, t)
        return pltpu.make_async_copy(c_hbm.at[blk, pl.ds(start, block_w)],
                                     buf.at[slot], sem.at[slot])

    @pl.when((nt > 0) & (base == 0))      # no earlier segment prefetched it
    def _first():
        copy(s, 0, 0).start()

    q = q_ref[...].astype(jnp.float32)               # (G, d)
    g = q.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (g, block_w), 1)

    def tile(t, carry):
        pv, pi = carry
        slot = (base + t) % 2
        copy(s, t, slot).wait()

        @pl.when(t + 1 < nt)
        def _next_tile():
            copy(s, t + 1, 1 - slot).start()

        @pl.when((t + 1 == nt) & (nxt < n_seg))
        def _next_segment():
            copy(nxt, 0, 1 - slot).start()

        c = buf[slot].astype(jnp.float32)            # (bw, d)
        if mxu:
            ct = c.T                                 # (d, bw)
            cross = jax.lax.dot_general(
                q, ct, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=matmul_precision(jnp.float32))
            csq = jnp.sum(ct * ct, axis=0, keepdims=True)   # (1, bw)
        else:
            cross = jnp.sum(q[:, None, :] * c[None, :, :], axis=-1)
            csq = jnp.sum(c * c, axis=-1)[None, :]
        seen = source(s, t)[2]
        slots = t * block_w - seen + lane
        keep = (lane >= seen) & (slots < n)
        score = jnp.where(keep, csq - 2.0 * cross, _INF)
        return _select_l_best(pv, pi, score, slots, l)

    pv, pi = jax.lax.fori_loop(
        0, nt, tile, (jnp.full((g, l), _INF, jnp.float32),
                      jnp.zeros((g, l), jnp.int32)))
    v_ref[...] = pv
    i_ref[...] = pi


def flash_scan_lists_raw(qg: Array, payload: Array, seg_count: Array,
                         seg_base: Array, seg_next: Array, blocks: Array, *,
                         l: int, block_w: int, paged: bool,
                         interpret: bool = False) -> tuple[Array, Array]:
    """Pallas call of the list-major scan, one grid step per segment.

    qg: (S, G, d) query groups; payload: the store's row blocks
    ``(A, R, d)``, ``B_W <= R``, left in HBM and copied tile by tile;
    seg_count: (S,) int32 real rows of each segment's list (0 on padding
    segments); seg_base: (S,) int32 tiles of all earlier segments;
    seg_next: (S,) int32 the next segment with rows (``S`` if none);
    blocks: int32, ``(S,)`` the payload block holding each segment's
    list as contiguous rows, or with ``paged`` ``(S·W,)`` the payload
    block (one page, ``B_W = R``) of each (segment, tile). Returns
    ``(slots int32 (S, G, l), scores f32 (S, G, l))`` ascending by
    (score, slot); slots index the list's own rows.
    """
    s_n, g, d = qg.shape
    kernel = functools.partial(
        _flash_scan_lists_kernel, block_w=block_w,
        n_w=blocks.shape[0] // s_n, l=l, paged=paged, mxu=not interpret)
    return pl.pallas_call(
        kernel,
        name="flash_scan_lists",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s_n,),
            in_specs=[
                pl.BlockSpec((None, g, d), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((None, g, l), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((None, g, l), lambda s, *_: (s, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, block_w, d), payload.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((s_n, g, l), jnp.int32),
            jax.ShapeDtypeStruct((s_n, g, l), jnp.float32),
        ],
        # the copies chain from one segment to the next: steps in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(seg_count, seg_base, seg_next, blocks, qg, payload)


def flash_probe_grouped_q8_raw(qp: Array, codes: Array, scales: Array, *,
                               l: int, block_b: int, block_w: int,
                               interpret: bool = False
                               ) -> tuple[Array, Array]:
    """Pallas call on pre-padded inputs (the quantized scan).

    qp: (nprobe, B_pad, d) f32 per-probe shifted queries, codes:
    (B_pad, nprobe, W_pad, d) int8, scales: (nprobe, B_pad, W_pad) f32
    with B_pad % block_b == W_pad % block_w == 0; padding slots must
    carry scale 0.0. The probe axis leads the query-side arrays so every
    block's two minor dims are whole ``(B, d)`` / ``(B, W)`` tiles.
    Returns ``(indices int32 (B_pad, l), dists f32 (B_pad, l))`` —
    indices into the flattened (nprobe·W_pad) candidate axis, dists the
    true quantized squared distances.
    """
    nprobe, b_pad, d = qp.shape
    w_pad = codes.shape[2]
    grid = (b_pad // block_b, nprobe, w_pad // block_w)

    kernel = functools.partial(
        _flash_probe_grouped_q8_kernel, block_w=block_w, w_total=w_pad,
        l=l)

    return pl.pallas_call(
        kernel,
        name="flash_probe_grouped_q8",
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_b, d), lambda i, p, w: (p, i, 0)),
            pl.BlockSpec((block_b, None, block_w, d),
                         lambda i, p, w: (i, p, w, 0)),
            pl.BlockSpec((None, block_b, block_w),
                         lambda i, p, w: (p, i, w)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, l), lambda i, p, w: (i, 0)),
            pl.BlockSpec((block_b, l), lambda i, p, w: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, l), jnp.int32),
            jax.ShapeDtypeStruct((b_pad, l), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, l), jnp.float32),
            pltpu.VMEM((block_b, l), jnp.int32),
        ],
        interpret=interpret,
    )(qp, codes, scales)


def flash_probe_grouped_raw(q: Array, c: Array, *, l: int, block_b: int,
                            block_c: int, c_actual: int,
                            interpret: bool = False) -> tuple[Array, Array]:
    """Pallas call on batch-padded inputs (the posting-list scan).

    q: (B_pad, d), c: (B_pad, C, d) with B_pad % block_b == 0 and
    ``l <= c_actual``. C need not divide into ``block_c`` tiles: the last
    tile reads past the end and the kernel masks candidates
    ``>= c_actual``. Returns ``(indices int32 (B_pad, l), scores f32
    (B_pad, l))`` — indices are positions into each query's own
    candidate axis.
    """
    b_pad, d = q.shape
    grid = (b_pad // block_b, pl.cdiv(c.shape[1], block_c))

    kernel = functools.partial(
        _flash_probe_grouped_kernel, block_c=block_c, c_actual=c_actual, l=l)

    return pl.pallas_call(
        kernel,
        name="flash_probe_grouped",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, c: (i, 0)),
            pl.BlockSpec((block_b, block_c, d), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, l), lambda i, c: (i, 0)),
            pl.BlockSpec((block_b, l), lambda i, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, l), jnp.int32),
            jax.ShapeDtypeStruct((b_pad, l), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, l), jnp.float32),
            pltpu.VMEM((block_b, l), jnp.int32),
        ],
        interpret=interpret,
    )(q, c)


def flash_probe_raw(q: Array, c: Array, *, l: int, block_n: int,
                    block_k: int, k_actual: int, c_sq: Array | None = None,
                    interpret: bool = False) -> tuple[Array, Array]:
    """Pallas call on pre-padded inputs.

    q: (N_pad, d), c: (K_pad, d) with N_pad % block_n == K_pad % block_k
    == 0 and ``l <= k_actual``. ``c_sq``: optional (1, K_pad) f32 of
    precomputed ``||c||^2`` (derived here when absent — pass the cached
    strip to skip the per-call reduction). Returns ``(indices int32
    (N_pad, l), scores f32 (N_pad, l))`` sorted ascending per row, where
    score is ``||c||^2 - 2 q.c`` (add ``||q||^2`` for the true squared
    distance).
    """
    n_pad, d = q.shape
    k_pad = c.shape[0]
    grid = (n_pad // block_n, k_pad // block_k)
    if c_sq is None:
        c32 = c.astype(jnp.float32)
        c_sq = jnp.sum(c32 * c32, axis=-1)[None, :]

    kernel = functools.partial(
        _flash_probe_kernel, block_k=block_k, k_actual=k_actual, l=l)

    return pl.pallas_call(
        kernel,
        name="flash_probe",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, k: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, k: (k, 0)),
            pl.BlockSpec((1, block_k), lambda i, k: (0, k)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, l), lambda i, k: (i, 0)),
            pl.BlockSpec((block_n, l), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, l), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, l), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, l), jnp.float32),
            pltpu.VMEM((block_n, l), jnp.int32),
        ],
        interpret=interpret,
    )(q, c, c_sq)
