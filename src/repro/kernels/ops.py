"""jit'd public wrappers around the flash-kmeans Pallas kernels.

Handles shape padding to tile multiples, platform dispatch (interpret mode
on the CPU backend, compiled Pallas on TPU, an error elsewhere), batching,
and the host-side prologue of the sort-inverse update (argsort + row
gather + tile-pair compaction).

Block resolution: every wrapper accepts an optional ``plan=``
(``core.plan.KernelPlan``) and/or explicit ``block_*`` overrides. When
neither is given the process-wide ``KernelPlanner`` plans the dispatch —
memoized per shape bucket, persisted on disk, hardware-detected — so no
wrapper carries magic block defaults. Whatever the source, the tiles are
audited against the hardware VMEM capacity (``core.heuristics``
footprints) and auto-shrunk with a warning rather than lowered into a
kernel that cannot fit.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import flash_assign as _fa
from repro.kernels import flash_lloyd as _fl
from repro.kernels import flash_probe as _fp
from repro.kernels import ref as _ref
from repro.kernels import sort_inverse_update as _siu

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """Tile shapes for the kernels (see core.heuristics for selection)."""
    assign_block_n: int = 256
    assign_block_k: int = 256
    update_block_n: int = 512
    update_block_k: int = 256
    fused_block_n: int = 256
    fused_block_k: int = 256

    def validate(self) -> "BlockConfig":
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v <= 0 or (v & (v - 1)) != 0 and v % 128 != 0:
                raise ValueError(f"{f.name}={v} must be a positive power of "
                                 "two or a multiple of 128")
        return self


def default_interpret() -> bool:
    """Compiled Mosaic kernels on a TPU, the Pallas interpreter on the CPU
    backend (tests, CPU debugging). Any other backend is an error: no
    kernel silently falls back to the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels run on a TPU, or interpreted "
                       f"on the CPU backend; got backend {backend!r}")


def _plan_leg(plan, leg: str) -> tuple[int, int]:
    """Extract the tile dims a wrapper needs from a ``KernelPlan``."""
    if plan.op == leg:
        return plan.blocks
    if plan.block is not None and leg in ("assign", "update", "fused"):
        b = plan.block
        return {"assign": (b.assign_block_n, b.assign_block_k),
                "update": (b.update_block_n, b.update_block_k),
                "fused": (b.fused_block_n, b.fused_block_k)}[leg]
    raise ValueError(
        f"a plan for op {plan.op!r} cannot drive the {leg!r} kernel")


def _resolve_blocks(op: str, shape: tuple, dtype, block_n: int | None,
                    block_k: int | None, plan, leg: str | None = None
                    ) -> tuple[int, int]:
    """Fill missing tile dims from ``plan`` (or the default planner).

    Explicit ``block_*`` arguments always win; a provided ``plan`` covers
    the rest; with neither, the process-wide ``KernelPlanner`` plans the
    dispatch (runs at trace time only — the result is a cache hit for
    every repeat of the shape bucket).
    """
    if block_n is not None and block_k is not None:
        return block_n, block_k
    if plan is None:
        from repro.core.plan import default_planner
        plan = default_planner().plan(op, shape, dtype)
    pn, pk = _plan_leg(plan, leg or op)
    return (pn if block_n is None else block_n,
            pk if block_k is None else block_k)


def _audit_blocks(op: str, bn: int, bk: int, d: int, itemsize: int, *,
                  k: int | None = None, l: int | None = None,
                  hw_name: str | None = None) -> tuple[int, int]:
    """VMEM footprint audit: the resolved tiles must fit the hardware.

    The closed-form choosers always respect the budget, but explicit
    ``block_*`` arguments (or stale plans replayed on a larger ``d``) can
    demand more VMEM than the chip has. Auto-shrinks (halving the larger
    tile dim first) with a clear warning; raises only when even minimal
    ``(8, 8)`` tiles cannot fit — that working set is irreducible (e.g.
    the fused kernel's resident ``K·d`` accumulator), so the caller must
    change dataflow, not tiles.

    ``hw_name`` pins the chip to audit against (a supplied plan's
    ``plan.hw`` — its tiles were sized for *that* VMEM, not the default
    planner's); ``None`` audits against the detected hardware.
    """
    from repro.core import heuristics as H
    from repro.core import plan as _planmod
    hw = _planmod.hardware_by_name(hw_name)

    def fp(a: int, b: int) -> int:
        if op == "assign":
            return H.assign_footprint(a, b, d, itemsize)
        if op == "update":
            return H.update_footprint(a, b, d, itemsize)
        if op == "fused":
            return H.fused_footprint(a, b, d, itemsize, _round_up(k, b))
        l_pad = _round_up(max(1, l), 8)
        if op == "probe":
            return H.probe_footprint(a, b, l_pad, d, itemsize)
        if op == "scan_q8":
            return H.scan_q8_footprint(a, b, l_pad, d)
        return H.scan_footprint(a, b, l_pad, d, itemsize)

    ceiling = hw.vmem_bytes
    orig = (bn, bk)
    over = fp(bn, bk)
    while fp(bn, bk) > ceiling:
        if bk > 8 and (bk >= bn or bn <= 8):
            bk //= 2
        elif bn > 8:
            bn //= 2
        else:
            raise ValueError(
                f"{op} kernel working set ({fp(bn, bk)} bytes) exceeds "
                f"{hw.name} VMEM ({ceiling} bytes) even at minimal (8, 8) "
                f"tiles for d={d}"
                + (f", K={k}" if op == "fused" else "")
                + "; this dataflow cannot be tiled onto the chip — use the "
                "two-pass path / reduce d")
    if (bn, bk) != orig:
        warnings.warn(
            f"{op} blocks {orig} exceed the {hw.name} VMEM footprint "
            f"budget ({over} > {ceiling} bytes) for d={d}; auto-shrunk to "
            f"({bn}, {bk}) — drop the explicit block_* overrides to let "
            "the KernelPlanner choose feasible tiles", stacklevel=3)
    return bn, bk


def _pad_to(x: Array, mult: int, axis: int, value) -> Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# FlashAssign
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_n", "block_k",
                                             "plan", "interpret",
                                             "want_dists"))
def flash_assign(x: Array, c: Array, *, block_n: int | None = None,
                 block_k: int | None = None, plan=None,
                 interpret: bool | None = None,
                 want_dists: bool = True) -> tuple[Array, Array]:
    """Fused assignment. x: (N, d), c: (K, d).

    Returns ``(assignments int32 (N,), min_sq_dists f32 (N,))``. Distances
    are true squared Euclidean distances (the ``||x||^2`` term is re-added
    outside the kernel); pass ``want_dists=False`` to skip that add.
    Blocks come from ``plan``/``block_*`` or the default ``KernelPlanner``.
    """
    if interpret is None:
        interpret = default_interpret()
    n, d = x.shape
    k = c.shape[0]
    block_n, block_k = _resolve_blocks("assign", (n, k, d), x.dtype,
                                       block_n, block_k, plan)
    block_n = min(block_n, _round_up(n, 8))
    block_k = min(block_k, _round_up(k, 8))
    block_n, block_k = _audit_blocks("assign", block_n, block_k, d,
                                     x.dtype.itemsize,
                                     hw_name=plan.hw if plan else None)
    xp = _pad_to(x, block_n, 0, 0)
    cp = _pad_to(c, block_k, 0, 0)
    a, m = _fa.flash_assign_raw(xp, cp, block_n=block_n, block_k=block_k,
                                k_actual=k, interpret=interpret)
    a, m = a.reshape(-1)[:n], m.reshape(-1)[:n]
    if want_dists:
        x32 = x.astype(jnp.float32)
        m = m + jnp.sum(x32 * x32, axis=-1)
        m = jnp.maximum(m, 0.0)  # clamp tiny negative fp residue
    return a, m


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# Sort-Inverse Update
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "block_n", "block_k",
                                             "plan", "interpret"))
def sort_inverse_update(x: Array, a: Array, *, k: int,
                        block_n: int | None = None,
                        block_k: int | None = None, plan=None,
                        interpret: bool | None = None
                        ) -> tuple[Array, Array]:
    """Contention-free centroid statistics. x: (N, d), a: (N,) int32.

    Returns ``(sums f32 (K, d), counts f32 (K,))`` — exact (up to f32
    accumulation order) equals of the scatter reference.
    """
    if interpret is None:
        interpret = default_interpret()
    n, d = x.shape
    block_n, block_k = _resolve_blocks("update", (n, k, d), x.dtype,
                                       block_n, block_k, plan)
    block_n = min(block_n, _round_up(n, 8))
    block_k = min(block_k, _round_up(k, 8))
    block_n, block_k = _audit_blocks("update", block_n, block_k, d,
                                     x.dtype.itemsize,
                                     hw_name=plan.hw if plan else None)
    k_tiles = _round_up(k, block_k) // block_k

    # 1) sort the 1-D assignment vector only (cheap: 4-byte keys).
    sorted_idx = jnp.argsort(a).astype(jnp.int32)
    a_sorted = jnp.take(a, sorted_idx)

    # 2) pad points into the dummy k-tile, then one streaming row gather.
    pad_id = jnp.int32(k_tiles * block_k)
    a_sorted = _pad_to(a_sorted, block_n, 0, pad_id)
    sorted_idx = _pad_to(sorted_idx, block_n, 0, 0)
    x_sorted = jnp.take(x, sorted_idx, axis=0)        # (N_pad, d)
    # zero padded rows so the dummy gather of row 0 contributes nothing
    n_pad = a_sorted.shape[0]
    row_valid = jnp.arange(n_pad) < n
    x_sorted = jnp.where(row_valid[:, None], x_sorted, 0)

    n_tiles = n_pad // block_n
    pair_n, pair_k = _siu.build_tile_pairs(
        a_sorted, block_n=block_n, block_k=block_k,
        n_tiles=n_tiles, k_tiles=k_tiles)

    s_pad, cnt_pad = _siu.sort_inverse_update_raw(
        x_sorted, a_sorted, pair_n, pair_k,
        block_n=block_n, block_k=block_k, k_tiles=k_tiles,
        interpret=interpret)
    cnt_pad = cnt_pad.reshape(-1)
    # k-tiles with no intersecting point tile are never visited by the
    # kernel grid — their output blocks are uninitialized. Zero them.
    visited = jnp.zeros((k_tiles + 1,), jnp.bool_).at[pair_k].set(True)
    row_tile = jnp.arange((k_tiles + 1) * block_k) // block_k
    live = visited[row_tile]
    s_pad = jnp.where(live[:, None], s_pad, 0.0)
    cnt_pad = jnp.where(live, cnt_pad, 0.0)
    return s_pad[:k], cnt_pad[:k]


# ---------------------------------------------------------------------------
# FlashLloyd — fused assignment + statistics in one pass
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_n", "block_k",
                                             "plan", "interpret"))
def flash_lloyd_step(x: Array, c: Array, *, block_n: int | None = None,
                     block_k: int | None = None, plan=None,
                     interpret: bool | None = None
                     ) -> tuple[Array, Array, Array, Array]:
    """Fused Lloyd statistics. x: (N, d), c: (K, d).

    Returns ``(assignments int32 (N,), sums f32 (K, d), counts f32 (K,),
    inertia f32 ())`` in a single pass over ``x`` — no argsort, no
    ``x_sorted`` gather, no second HBM stream. The ``(K_pad, d)`` f32
    accumulator must be VMEM-resident; callers should consult the
    ``KernelPlanner``'s step plan (``plan("step", ...).impl`` falls back
    to the two-pass assign + sort-inverse pipeline when it does not fit).
    """
    if interpret is None:
        interpret = default_interpret()
    n, d = x.shape
    k = c.shape[0]
    block_n, block_k = _resolve_blocks("step", (n, k, d), x.dtype,
                                       block_n, block_k, plan, leg="fused")
    block_n = min(block_n, _round_up(n, 8))
    block_k = min(block_k, _round_up(k, 8))
    block_n, block_k = _audit_blocks("fused", block_n, block_k, d,
                                     x.dtype.itemsize, k=k,
                                     hw_name=plan.hw if plan else None)
    xp = _pad_to(x, block_n, 0, 0)
    cp = _pad_to(c, block_k, 0, 0)
    a, s, cnt, j = _fl.flash_lloyd_raw(
        xp, cp, block_n=block_n, block_k=block_k, k_actual=k, n_actual=n,
        interpret=interpret)
    return a.reshape(-1)[:n], s[:k], cnt.reshape(-1)[:k], j[0, 0]


# ---------------------------------------------------------------------------
# FlashProbe — fused distance + online top-L (IVF search primitive)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("l", "block_n", "block_k",
                                             "plan", "interpret",
                                             "want_dists"))
def flash_probe(q: Array, c: Array, *, l: int, block_n: int | None = None,
                block_k: int | None = None, plan=None,
                interpret: bool | None = None,
                want_dists: bool = True,
                c_sq: Array | None = None) -> tuple[Array, Array]:
    """Fused L-nearest-centroid probe. q: (N, d), c: (K, d), ``l <= K``.

    Returns ``(indices int32 (N, l), dists f32 (N, l))`` sorted ascending
    by distance; ties broken toward the lower index (``jax.lax.top_k``
    parity). Distances are true squared Euclidean distances unless
    ``want_dists=False`` (then the ``||q||^2``-free score is returned).

    ``c_sq``: optional precomputed ``||c||^2`` f32 of shape (K,) — pass
    the cached strip (e.g. ``IVFIndex`` keeps one per centroid set) to
    skip the per-call norm reduction; when absent it is derived here.

    ``l`` is padded up to a sublane multiple internally (the kernel's
    running-state minor dim); the extra slots hold the (l+1)-th..best
    candidates and are sliced off — a superset, never a different answer.
    """
    if interpret is None:
        interpret = default_interpret()
    n, d = q.shape
    k = c.shape[0]
    if l > k:
        raise ValueError(f"flash_probe needs l <= K, got l={l} > K={k}")
    if l < 1:
        raise ValueError(f"flash_probe needs l >= 1, got l={l}")
    l_pad = _round_up(l, 8)
    block_n, block_k = _resolve_blocks("probe", (n, k, d, l), q.dtype,
                                       block_n, block_k, plan)
    block_n = min(block_n, _round_up(n, 8))
    block_k = min(block_k, _round_up(k, 8))
    block_n, block_k = _audit_blocks("probe", block_n, block_k, d,
                                     q.dtype.itemsize, l=l,
                                     hw_name=plan.hw if plan else None)
    qp = _pad_to(q, block_n, 0, 0)
    cp = _pad_to(c, block_k, 0, 0)
    if c_sq is None:
        c32 = cp.astype(jnp.float32)
        csq = jnp.sum(c32 * c32, axis=-1)[None, :]
    else:
        csq = _pad_to(c_sq.astype(jnp.float32)[None, :], block_k, 1, 0.0)
    idx, v = _fp.flash_probe_raw(qp, cp, l=l_pad, block_n=block_n,
                                 block_k=block_k, k_actual=k, c_sq=csq,
                                 interpret=interpret)
    idx, v = idx[:n, :l], v[:n, :l]
    if want_dists:
        q32 = q.astype(jnp.float32)
        v = v + jnp.sum(q32 * q32, axis=-1, keepdims=True)
        v = jnp.maximum(v, 0.0)  # clamp tiny negative fp residue
    return idx, v


@functools.partial(jax.jit, static_argnames=("l", "block_b", "block_c",
                                             "plan", "interpret",
                                             "want_dists"))
def flash_probe_grouped(q: Array, c: Array, *, l: int,
                        block_b: int | None = None,
                        block_c: int | None = None, plan=None,
                        interpret: bool | None = None,
                        want_dists: bool = True) -> tuple[Array, Array]:
    """Per-query-candidate top-L scan. q: (B, d), c: (B, C, d).

    The IVF posting-list scan: query ``i`` is scored against its own
    gathered candidate block ``c[i]`` (C = nprobe·cap rows), one query
    *tile* per grid step — a single kernel launch for the whole batch,
    no ``B x C`` score matrix in HBM. Returns ``(indices int32 (B, l),
    dists f32 (B, l))`` ascending; indices address each query's own
    candidate axis.
    """
    if interpret is None:
        interpret = default_interpret()
    b, d = q.shape
    c_n = c.shape[1]
    if l > c_n:
        raise ValueError(f"flash_probe_grouped needs l <= C, got l={l} "
                         f"> C={c_n}")
    if l < 1:
        raise ValueError(f"flash_probe_grouped needs l >= 1, got l={l}")
    l_pad = _round_up(l, 8)
    block_b, block_c = _resolve_blocks("scan", (b, c_n, d, l), q.dtype,
                                       block_b, block_c, plan)
    block_b = min(block_b, _round_up(b, 8))
    block_c = min(block_c, _round_up(c_n, 8))
    block_b, block_c = _audit_blocks("scan", block_b, block_c, d,
                                     q.dtype.itemsize, l=l,
                                     hw_name=plan.hw if plan else None)
    qp = _pad_to(q, block_b, 0, 0)
    # the candidate axis is not padded: a copy of the (B, C, d) block can
    # be as large as the block itself; the kernel masks the ragged tail
    cp = _pad_to(c, block_b, 0, 0)
    idx, v = _fp.flash_probe_grouped_raw(
        qp, cp, l=l_pad, block_b=block_b, block_c=block_c, c_actual=c_n,
        interpret=interpret)
    idx, v = idx[:b, :l], v[:b, :l]
    if want_dists:
        q32 = q.astype(jnp.float32)
        v = v + jnp.sum(q32 * q32, axis=-1, keepdims=True)
        v = jnp.maximum(v, 0.0)
    return idx, v


@functools.partial(jax.jit, static_argnames=("l", "block_b", "block_w",
                                             "plan", "interpret"))
def flash_probe_grouped_q8(qp: Array, codes: Array, scales: Array, *,
                           l: int, block_b: int | None = None,
                           block_w: int | None = None, plan=None,
                           interpret: bool | None = None
                           ) -> tuple[Array, Array]:
    """Quantized per-query-candidate top-L scan (dequant in VMEM).

    qp: (B, nprobe, d) f32 per-probe shifted queries
    (``q - anchor[cell]``), codes: (B, nprobe, W, d) int8 residual
    codes, scales: (B, nprobe, W) f32 per-slot scales (exactly 0.0 on
    empty/padded slots). Returns ``(indices int32 (B, l), dists f32
    (B, l))`` ascending — indices address the flattened unpadded
    ``nprobe·W`` candidate axis in probe-rank-major order (the fp32
    scan's ordering), dists are true quantized squared distances
    (nothing to re-add). Rows with fewer than ``l`` live candidates
    pad with ``+inf`` dists — callers mask those before trusting ids.
    """
    if interpret is None:
        interpret = default_interpret()
    b, nprobe, d = qp.shape
    w = codes.shape[2]
    c_n = nprobe * w
    if l > c_n:
        raise ValueError(f"flash_probe_grouped_q8 needs l <= nprobe*W, "
                         f"got l={l} > {c_n}")
    if l < 1:
        raise ValueError(f"flash_probe_grouped_q8 needs l >= 1, got l={l}")
    l_pad = _round_up(l, 8)
    block_b, block_w = _resolve_blocks("scan_q8", (b, c_n, d, l),
                                       codes.dtype, block_b, block_w, plan)
    block_b = min(block_b, _round_up(b, 8))
    block_w = min(block_w, _round_up(w, 8))
    block_b, block_w = _audit_blocks("scan_q8", block_b, block_w, d,
                                     codes.dtype.itemsize, l=l,
                                     hw_name=plan.hw if plan else None)
    # probe axis leading on the query side (small arrays; the code
    # stream keeps its gathered layout)
    qpp = jnp.swapaxes(_pad_to(qp, block_b, 0, 0), 0, 1)
    cp = _pad_to(_pad_to(codes, block_b, 0, 0), block_w, 2, 0)
    sp = jnp.swapaxes(
        _pad_to(_pad_to(scales, block_b, 0, 0), block_w, 2, 0.0), 0, 1)
    w_pad = cp.shape[2]
    idx, v = _fp.flash_probe_grouped_q8_raw(
        qpp, cp, sp, l=l_pad, block_b=block_b, block_w=block_w,
        interpret=interpret)
    idx, v = idx[:b, :l], v[:b, :l]
    # kernel indices address the padded W axis; remap to the unpadded
    # candidate layout the caller gathered (probe-rank major)
    idx = (idx // w_pad) * w + jnp.minimum(idx % w_pad, w - 1)
    return idx, v


@functools.partial(jax.jit, static_argnames=("l", "block_w", "interpret"))
def flash_scan_lists(qg: Array, payload: Array, seg_count: Array,
                     blocks: Array, *, l: int, block_w: int,
                     interpret: bool | None = None) -> tuple[Array, Array]:
    """List-major posting-list scan: each segment's query group against
    its list's rows, streamed from the store in place.

    qg: (S, G, d) query groups; payload: (A, R, d) store row blocks,
    ``block_w <= R``; seg_count: (S,) int32 real rows of each segment's
    list, 0 on padding segments; blocks: int32 where each list lives, as
    ``index.store.list_blocks`` lays it out — ``(S,)``: segment ``s``'s
    list is contiguous rows of ``payload[blocks[s]]``, read in tiles of
    ``block_w`` rows; ``(S, W)``: tile ``t`` is the whole block
    ``payload[blocks[s, t]]`` (a page, ``block_w == R``). ``G`` and
    ``block_w`` come from the planner's ``list_scan`` op (the caller
    builds the groups, so neither is re-chosen here). Returns ``(slots
    int32 (S, G, l), scores f32 (S, G, l))`` ascending by (score, slot),
    the score ``||c||^2 - 2 q.c`` (no ``||q||^2``); where a list holds
    fewer than ``l`` rows the rest score ``+inf``.
    """
    if interpret is None:
        interpret = default_interpret()
    if l < 1:
        raise ValueError(f"flash_scan_lists needs l >= 1, got l={l}")
    s_n, g, d = qg.shape
    paged = blocks.ndim == 2
    if (blocks.shape[0] != s_n or block_w > payload.shape[1]
            or (paged and block_w != payload.shape[1])):
        raise ValueError(
            f"flash_scan_lists: blocks {blocks.shape} for {s_n} segments, "
            f"tile {block_w} over blocks of {payload.shape[1]} rows")
    from repro.core import heuristics as H
    from repro.core import plan as _planmod
    hw = _planmod.hardware_by_name(None)
    need = H.list_scan_footprint(g, block_w, _round_up(l, 8), d,
                                 payload.dtype.itemsize)
    if need > hw.vmem_bytes:
        raise ValueError(
            f"list scan tiles (G={g}, B_W={block_w}) need {need} bytes of "
            f"{hw.name} VMEM ({hw.vmem_bytes}) at d={d}")
    seg_count = seg_count.astype(jnp.int32)
    tiles = (seg_count + block_w - 1) // block_w
    base = (jnp.cumsum(tiles) - tiles).astype(jnp.int32)
    # the next segment with rows: a reversed running minimum of their ids
    ids = jnp.where(tiles > 0, jnp.arange(s_n, dtype=jnp.int32), s_n)
    ahead = jax.lax.cummin(ids, reverse=True)
    nxt = jnp.concatenate([ahead[1:], jnp.full((1,), s_n, jnp.int32)])
    return _fp.flash_scan_lists_raw(
        qg, payload, seg_count, base, nxt,
        blocks.reshape(-1).astype(jnp.int32), l=l, block_w=block_w,
        paged=paged, interpret=interpret)


# ---------------------------------------------------------------------------
# Batched variants + centroid update convenience
# ---------------------------------------------------------------------------

def flash_assign_batched(x: Array, c: Array, **kw) -> tuple[Array, Array]:
    """x: (B, N, d), c: (B, K, d) — per-batch centroids (paper's B axis)."""
    return jax.vmap(lambda xb, cb: flash_assign(xb, cb, **kw))(x, c)


def sort_inverse_update_batched(x: Array, a: Array, *, k: int, **kw
                                ) -> tuple[Array, Array]:
    return jax.vmap(lambda xb, ab: sort_inverse_update(xb, ab, k=k, **kw))(x, a)


def centroid_stats(x: Array, a: Array, *, k: int, impl: str = "sort_inverse",
                   block_n: int | None = None, block_k: int | None = None,
                   plan=None, interpret: bool | None = None
                   ) -> tuple[Array, Array]:
    """Centroid sufficient statistics ``(sums f32 (K, d), counts f32 (K,))``
    by any of the two-pass update dataflows."""
    if impl == "sort_inverse":
        return sort_inverse_update(x, a, k=k, block_n=block_n,
                                   block_k=block_k, plan=plan,
                                   interpret=interpret)
    if impl == "scatter":
        return _ref.update_scatter_ref(x, a, k)
    if impl == "dense_onehot":
        return _ref.update_dense_onehot_ref(x, a, k)
    raise ValueError(f"unknown update impl {impl!r}")


def finalize_centroids(s: Array, cnt: Array, c_prev: Array) -> Array:
    """sums/counts -> centroids with empty-cluster fallback (keep old).

    Counts may be fractional (decayed streaming statistics), so the safe
    denominator must preserve ``s / cnt`` for any ``cnt > 0`` — clamping
    to 1 would shrink low-weight centroids toward the origin.
    """
    new_c = s / jnp.where(cnt > 0, cnt, 1.0)[:, None]
    return jnp.where((cnt > 0)[:, None], new_c,
                     c_prev.astype(jnp.float32)).astype(c_prev.dtype)


def centroid_update(x: Array, a: Array, c_prev: Array, *,
                    impl: str = "sort_inverse", block_n: int | None = None,
                    block_k: int | None = None, plan=None,
                    interpret: bool | None = None) -> Array:
    """Full update stage with empty-cluster fallback (keeps old centroid)."""
    s, cnt = centroid_stats(x, a, k=c_prev.shape[0], impl=impl,
                            block_n=block_n, block_k=block_k, plan=plan,
                            interpret=interpret)
    return finalize_centroids(s, cnt, c_prev)
