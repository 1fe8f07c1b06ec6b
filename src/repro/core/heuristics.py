"""Cache-aware compile heuristic (paper §4.3), re-derived for TPU.

The paper picks GPU kernel configurations analytically from L1/L2 cache
sizes and the problem shape instead of exhaustive autotuning. The TPU
analogue: pick Pallas block shapes from the VMEM capacity and MXU/VPU
alignment rules in closed form.

Selection model (per kernel):
  - tiles must be lane-aligned (128) on the minor matmul dims and
    sublane-aligned (8) elsewhere;
  - the resident working set (input tiles double-buffered by the Pallas
    pipeline + f32 intermediates + output/accumulator tiles) must fit a
    conservative fraction of VMEM;
  - subject to that, maximize MXU utilization: prefer B_K, B_N >= 128 and
    grow the streamed dimension first (more reuse of the resident tile).

This module is also the single source of truth for the hardware constants
used by the roofline analysis.
"""
from __future__ import annotations

import dataclasses
import math

from repro.kernels.ops import BlockConfig


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    vmem_bytes: int          # per-core VMEM
    lane: int                # vector lane count (minor tile alignment)
    sublane: int             # sublane count
    mxu: int                 # systolic array dim
    flops_bf16: float        # peak FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    ici_bw: float            # bytes/s per link
    hbm_bytes: int           # HBM capacity per chip
    h2d_bw: float            # host->device bytes/s (PCIe analogue)


TPU_V5E = Hardware(
    name="tpu_v5e",
    vmem_bytes=16 * 2**20,
    lane=128,
    sublane=8,
    mxu=128,
    flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    hbm_bytes=16 * 2**30,
    h2d_bw=32e9,
)

TPU_V4 = Hardware(
    name="tpu_v4",
    vmem_bytes=16 * 2**20,
    lane=128,
    sublane=8,
    mxu=128,
    flops_bf16=275e12,
    hbm_bw=1228e9,
    ici_bw=50e9,
    hbm_bytes=32 * 2**30,
    h2d_bw=32e9,
)

TPU_V5P = Hardware(
    name="tpu_v5p",
    vmem_bytes=16 * 2**20,
    lane=128,
    sublane=8,
    mxu=128,
    flops_bf16=459e12,
    hbm_bw=2765e9,
    ici_bw=100e9,
    hbm_bytes=95 * 2**30,
    h2d_bw=32e9,
)

TPU_V6E = Hardware(
    name="tpu_v6e",
    vmem_bytes=32 * 2**20,
    lane=128,
    sublane=8,
    mxu=256,
    flops_bf16=918e12,
    hbm_bw=1640e9,
    ici_bw=50e9,
    hbm_bytes=32 * 2**30,
    h2d_bw=32e9,
)

# ``jax.devices()[0].device_kind`` (lowercased, spaces stripped) substring
# -> Hardware row. Ordered: first match wins, so the more specific names
# come first ("tpu v5 lite" must not match the bare-"v5" v5p row).
# ``core.plan.detect_hardware`` walks this table; a TPU that matches no
# row is an error, and only the CPU backend (interpret mode) plans
# against TPU_V5E.
HARDWARE_TABLE = (
    ("v6", TPU_V6E),
    ("v5p", TPU_V5P),
    ("v5lite", TPU_V5E),
    ("v5e", TPU_V5E),
    ("v5", TPU_V5P),
    ("v4", TPU_V4),
)

# Budget fraction: leave headroom for Pallas pipeline internals + spills.
_VMEM_FRACTION = 0.7
_CANDIDATE_TILES = (128, 256, 512, 1024, 2048)


def vmem_budget(hw: Hardware = TPU_V5E) -> int:
    """The soft VMEM budget the closed-form choosers plan against (the
    full ``hw.vmem_bytes`` is the hard ceiling the wrappers audit)."""
    return int(hw.vmem_bytes * _VMEM_FRACTION)


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def _fit_minor(limit: int, size: int, align: int) -> int:
    """Largest aligned tile <= limit covering at most size."""
    best = align
    for t in _CANDIDATE_TILES:
        if t <= limit and t <= _round_up(size, align):
            best = max(best, t)
    return best


def _mxu_split(elems: int, bytes_in: int) -> int:
    """VMEM the MXU's full-precision f32 contraction adds for ``elems``
    operand elements: each f32 operand is split into three bf16 parts
    (``kernels.flash_assign.matmul_precision``); other dtypes feed the
    MXU as they are."""
    return 3 * elems * 2 if bytes_in == 4 else 0


# The footprints below count what Mosaic allocates for one grid step:
# every pipelined block twice (double buffering, including the blocks
# whose index never changes), scratch, and the large vector temporaries.
# Each was checked against the scoped VMEM the TPU compiler needs at the
# planner's tiles (v5e, jax 0.9.0); tests/core/test_heuristics.py pins
# that the choosers stay inside the budget they model.

def assign_footprint(bn: int, bk: int, d: int, bytes_in: int) -> int:
    """VMEM bytes held live by one FlashAssign grid step."""
    x_tiles = 2 * bn * d * bytes_in     # point tile (double-buffered)
    c_tiles = 2 * bk * d * bytes_in     # centroid stream (double-buffered)
    mxu = _mxu_split((bn + bk) * d, bytes_in)
    csq = bk * d * 4                    # f32 c*c product
    score = bn * bk * 4                 # f32 (bk, bn) score tile
    state = bn * (4 + 4) * 8            # (1, bn) min/argmin rows
    out = 2 * bn * (4 + 4) * 8          # (1, bn) output rows
    return x_tiles + c_tiles + mxu + csq + score + state + out


def update_footprint(bn: int, bk: int, d: int, bytes_in: int) -> int:
    """VMEM bytes for one sort-inverse grid step."""
    x_tiles = 2 * bn * d * bytes_in     # double-buffered point stream
    ids = 2 * bn * 4 * 8                # (1, bn) id rows
    onehot = 4 * bn * bk * 4            # one-hot, its transpose, masks
    mxu = _mxu_split(bn * (bk + d), bytes_in)
    acc = 2 * bk * d * 4                # output block (double-buffered)
    partial = bk * d * 4
    cnt = 2 * bk * 4 * 8
    return x_tiles + ids + onehot + mxu + acc + partial + cnt


def fused_footprint(bn: int, bk: int, d: int, bytes_in: int,
                    k_pad: int) -> int:
    """VMEM bytes held live by one FlashLloyd grid step.

    The full centroid set and the f32 ``(K_pad, d)`` sums accumulator are
    resident across the whole grid — that ``K_pad·d`` term is the
    constraint the two-pass path does not have, and the reason the fused
    path only wins at small-to-moderate ``K·d`` (see DESIGN.md).
    """
    x_tiles = 2 * bn * d * bytes_in     # double-buffered point stream
    c_res = 2 * k_pad * d * bytes_in    # resident centroid block
    acc = 2 * (k_pad * d * 4 + k_pad * 4)   # resident f32 sums + counts
    sweep = 4 * bn * bk * 4             # score / ids / one-hot slices
    mxu = _mxu_split((bn + bk) * d, bytes_in)   # sweep 1's HIGHEST split
    # sweep 2 holds the tile's three exact bf16 terms (the one-hot is
    # exact in bf16 and is not split): ``kernels.flash_lloyd.split_bf16x3``
    terms = _mxu_split(bn * d, bytes_in)
    state = bn * (4 + 4) * 8 + 2 * bn * 4 * 8   # argmin rows + out row
    return x_tiles + c_res + acc + sweep + mxu + terms + state


def probe_footprint(bn: int, bk: int, l: int, d: int, bytes_in: int) -> int:
    """VMEM bytes held live by one FlashProbe grid step.

    Like FlashAssign but the running state is an L-best pool instead of a
    scalar argmin, and the selection rounds carry the tile's scores and
    the pool (values + indices) through a ``fori_loop``.
    """
    q_tiles = 2 * bn * d * bytes_in     # query tile (double-buffered)
    c_tiles = 2 * bk * d * bytes_in     # double-buffered stream
    mxu = _mxu_split((bn + bk) * d, bytes_in)
    score = bn * bk * 4                 # f32 intermediate
    select = 2 * bn * (l + bk) * 4      # round carry + temporaries
    state = bn * l * (4 + 4)            # running L-best scratch
    out = 2 * bn * l * (4 + 4)
    return q_tiles + c_tiles + mxu + score + select + state + out


def scan_footprint(bb: int, bc: int, l: int, d: int, bytes_in: int) -> int:
    """VMEM bytes held live by one grouped-probe (posting-list scan) grid
    step: the candidate stream carries a per-query leading axis, so its
    double-buffered tile costs ``2·B_B·B_C·d·b`` and the two f32 products
    the VPU reduces over d (``q·c`` and ``c·c``) as much again — the
    dominant terms."""
    q_tiles = 2 * bb * d * bytes_in
    c_tiles = 2 * bb * bc * d * bytes_in  # double-buffered per-query stream
    prods = 2 * bb * bc * d * 4         # f32 q·c and c·c products
    score = bb * bc * 4 * 2             # f32 score + csq intermediates
    select = 2 * bb * (l + bc) * 4      # selection round carry
    state = bb * l * (4 + 4)
    out = 2 * bb * l * (4 + 4)
    return q_tiles + c_tiles + prods + score + select + state + out


def list_scan_footprint(g: int, bw: int, l: int, d: int,
                        bytes_in: int) -> int:
    """VMEM bytes held live by one list-major scan grid step: a ``(G, d)``
    query group and a ``(B_W, d)`` list tile (both double-buffered), the
    tile's f32 transpose and its square for ``||c||^2``, the MXU's
    operand split, and the ``(G, B_W)`` score and selection carry."""
    q_tiles = 2 * g * d * bytes_in
    c_tiles = 2 * bw * d * bytes_in
    ct = 2 * bw * d * 4                 # f32 transpose + its square
    mxu = _mxu_split((g + bw) * d, 4)
    score = 2 * g * bw * 4              # cross term + masked score
    select = 2 * g * (l + bw) * 4
    state = g * l * (4 + 4)
    out = 2 * g * l * (4 + 4)
    return q_tiles + c_tiles + ct + mxu + score + select + state + out


# The list-major scan's cost model (``choose_list_scan_blocks``), fitted
# to a (G, B_W) sweep of the search_backlog unit on a v5e (PERF.md,
# section 6): a list tile costs one selection round's latency per kept
# result (the rounds' dependent lane reductions, whatever the tile's
# size) plus a share per (query, row) element once the tile is large.
# The tile's copy and MXU product hide under the rounds.
_LIST_ROUND_S = 0.29e-6
_LIST_ELEM_S = 2.9e-12


def choose_list_scan_blocks(pairs: int, k: int, width: int, d: int, l: int,
                            *, dtype_bytes: int = 4,
                            hw: Hardware = TPU_V5E) -> tuple[int, int]:
    """Closed-form ``(G, B_W)`` for the list-major scan: the pair of least
    modeled time that fits the VMEM budget.

    The model counts segments as at most one per probed list plus one
    per ``G`` (query, probe) pairs (``min(k, pairs) + pairs / G``: a
    larger group re-streams hot lists less), tiles per segment as a list
    of half the occupied ``width`` in ``B_W``-row tiles plus a ragged last
    one, and each tile as ``l`` selection rounds of latency plus a cost
    per element of the ``(G, B_W)`` tile. ``G`` is a sublane multiple up
    to one MXU width (and no more than the pairs), ``B_W`` a candidate
    tile no wider than the width; ties go to the smaller tiles.
    """
    budget = vmem_budget(hw)
    l_pad = _round_up(max(1, l), hw.sublane)
    w_lim = _round_up(max(1, width), hw.sublane)
    pairs = max(1, int(pairs))
    lists = min(max(1, int(k)), pairs)
    best, best_cost = (hw.sublane, hw.sublane), None
    g = hw.sublane
    while g <= min(hw.mxu, _round_up(pairs, hw.sublane)):
        segs = lists + pairs / g
        for bw in (hw.sublane,) + _CANDIDATE_TILES:
            if bw > w_lim and bw > hw.sublane:
                continue
            if list_scan_footprint(g, bw, l_pad, d, dtype_bytes) > budget:
                continue
            cost = (segs * (w_lim / 2 / bw + 0.5) * max(1, l)
                    * (_LIST_ROUND_S + _LIST_ELEM_S * g * bw))
            if best_cost is None or cost < best_cost:
                best, best_cost = (g, bw), cost
        g *= 2
    return best


def scan_q8_footprint(bb: int, bw: int, l: int, d: int) -> int:
    """VMEM bytes held live by one quantized grouped-scan grid step.

    The streamed candidate tile is int8 codes (``2·B_B·B_W·d·1``) plus a
    per-slot f32 scale strip; the kernel widens the codes to f32 in
    VMEM, so the f32 intermediates (``B_B·B_W·d·4`` each) — not the code
    stream — are the dominant VMEM term. That is the codec trade stated
    plainly: HBM traffic shrinks ~4x while the on-chip working set stays
    f32-sized.
    """
    q_tiles = 2 * bb * d * 4            # q' tile (f32, double-buffered)
    c_tiles = 2 * bb * bw * d * 1       # double-buffered int8 code stream
    s_tiles = 2 * bb * bw * 4           # double-buffered f32 scale strip
    deq = 3 * bb * bw * d * 4           # f32 codes + q·c and c·c products
    score = bb * bw * 4 * 2             # f32 score + csq intermediates
    select = 2 * bb * (l + bw) * 4      # selection round carry
    state = bb * l * (4 + 4)
    out = 2 * bb * l * (4 + 4)
    return q_tiles + c_tiles + s_tiles + deq + score + select + state + out


def choose_scan_q8_blocks(b: int, c: int, d: int, l: int, *,
                          hw: Hardware = TPU_V5E) -> tuple[int, int]:
    """Closed-form (block_b, block_w) for the quantized grouped scan —
    the same largest-feasible-area objective as ``choose_scan_blocks``,
    judged against the q8 footprint. The int8 code tile is cheap but the
    f32 dequant intermediate restores most of the pressure, so the
    feasible region is only modestly larger than the fp32 scan's."""
    budget = vmem_budget(hw)
    l_pad = _round_up(max(1, l), hw.sublane)
    b_lim = _round_up(b, hw.sublane)
    c_lim = _round_up(c, hw.lane)
    best = (hw.sublane, hw.lane)
    bb_cands = tuple(hw.sublane * 2**i for i in range(4)) + _CANDIDATE_TILES
    for bb in bb_cands:
        if bb > b_lim:
            continue
        for bw in _CANDIDATE_TILES:
            if bw > c_lim and bw > hw.lane:
                continue
            if scan_q8_footprint(bb, bw, l_pad, d) > budget:
                continue
            if (bb * bw, bw) > (best[0] * best[1], best[1]):
                best = (bb, bw)
    return best


def choose_scan_blocks(b: int, c: int, d: int, l: int, *,
                       dtype_bytes: int = 4, hw: Hardware = TPU_V5E
                       ) -> tuple[int, int]:
    """Closed-form (block_b, block_c) for the grouped posting-list scan.

    The candidate tile pays ``B_B·B_C·d`` bytes, so unlike the shared-
    centroid kernels the two block dims compete directly for VMEM. Grid
    steps number ``B·C / (B_B·B_C)`` while the per-byte selection work is
    nearly tile-shape-independent (``~B·C·L`` for ``B_C >> L``), so the
    right objective is simply the largest feasible tile *area*; ties go
    to the wider candidate dim (longer sweep per selection state, and
    the lane-aligned axis).
    """
    budget = vmem_budget(hw)
    l_pad = _round_up(max(1, l), hw.sublane)
    b_lim = _round_up(b, hw.sublane)
    c_lim = _round_up(c, hw.lane)
    best = (hw.sublane, hw.lane)
    bb_cands = tuple(hw.sublane * 2**i for i in range(4)) + _CANDIDATE_TILES
    for bb in bb_cands:
        if bb > b_lim:
            continue
        for bc in _CANDIDATE_TILES:
            if bc > c_lim and bc > hw.lane:
                continue
            if scan_footprint(bb, bc, l_pad, d, dtype_bytes) > budget:
                continue
            if (bb * bc, bc) > (best[0] * best[1], best[1]):
                best = (bb, bc)
    return best


# --- per-iteration HBM traffic models -------------------------------------
# Single source of truth for the runtime crossover below.

def assign_bytes_flash(n: int, k: int, d: int, b: int = 4) -> float:
    """FlashAssign: stream X once, C once (per point-tile reuse in VMEM),
    write assignments + min-dists."""
    return (n * d + k * d) * b + 2 * n * 4


def update_bytes_sort_inverse(n: int, k: int, d: int, b: int = 4) -> float:
    """argsort keys (2x4B ops on N) + one row-gather pass (read+write X)
    + streamed kernel read + (K,d) output merges."""
    sort_io = 4 * n * 4
    gather_io = 2 * n * d * b
    kernel_io = n * d * b + k * d * 4 + k * 4
    return sort_io + gather_io + kernel_io


def lloyd_bytes_fused(n: int, k: int, d: int, b: int = 4) -> float:
    """FlashLloyd per-iteration HBM traffic: stream X once, C once, write
    assignments + the (K,d)/(K,) statistics. No argsort, no x_sorted
    gather, no second pass over X."""
    return (n * d + k * d) * b + n * 4 + k * d * 4 + k * 4


def choose_step_impl(n: int, k: int, d: int, *, dtype_bytes: int = 4,
                     hw: Hardware = TPU_V5E,
                     blk: BlockConfig | None = None) -> str:
    """Fused-vs-two-pass crossover rule (DESIGN.md).

    ``"fused"`` requires both legs of the crossover:

    1. *feasibility* — the FlashLloyd working set, dominated by the
       ``K_pad·d·4`` f32 accumulator plus the resident centroid block,
       fits the VMEM budget at the heuristic's block shapes (the two-pass
       path only ever holds one ``B_K·d`` output block, so it scales to
       arbitrary ``K·d``);
    2. *roofline win* — the fused statistics sweep is FLOP-dense
       (``2NKd`` extra MXU work vs the sort-inverse block-sparse matmul),
       so at large ``K`` it turns compute-bound before the accumulator
       even stops fitting. Fuse only while the single-kernel roofline
       time beats the summed two-pass stages.

    ``blk`` overrides the heuristic's block shapes — pass the caller's
    explicit ``BlockConfig`` so feasibility is judged for the tiles that
    will actually be launched.
    """
    budget = vmem_budget(hw)
    if blk is None:
        blk = choose_blocks(n, k, d, dtype_bytes=dtype_bytes, hw=hw)
    k_pad = _round_up(k, blk.fused_block_k)
    if fused_footprint(blk.fused_block_n, blk.fused_block_k, d,
                       dtype_bytes, k_pad) > budget:
        return "two_pass"
    peak, bw = hw.flops_bf16, hw.hbm_bw
    # fused: one kernel, one Nd stream, assignment + dense one-hot FLOPs
    t_fused = max(4.0 * n * k * d / peak,
                  lloyd_bytes_fused(n, k, d, dtype_bytes) / bw)
    # two-pass: assign and update serialize on the HBM round trip
    t_assign = max(2.0 * n * k * d / peak,
                   assign_bytes_flash(n, k, d, dtype_bytes) / bw)
    t_update = max(2.0 * n * blk.update_block_k * d / peak,
                   update_bytes_sort_inverse(n, k, d, dtype_bytes) / bw)
    return "fused" if t_fused <= t_assign + t_update else "two_pass"


def probe_bytes_flash(n: int, k: int, d: int, l: int, b: int = 4) -> float:
    """FlashProbe HBM traffic: stream Q once, C once (per query-tile reuse
    in VMEM), write the (N, L) index/distance pair. The N x K score matrix
    never exists in HBM — the term a materialized top_k baseline pays
    twice (write + re-read)."""
    return (n * d + k * d) * b + 2 * n * l * 4


def route_group_cap(k: int, kc: int) -> int:
    """Modeled per-coarse-group fine-centroid capacity: the mean group
    size ``ceil(K / K_c)`` with 2x slack for imbalance, rounded to the
    next power of two (the router pads real groups the same way, so the
    planner's byte model and the launched gather agree in shape). Floor 8
    = one sublane."""
    mean = (k + kc - 1) // max(1, kc)
    return max(8, 1 << (max(1, 2 * mean) - 1).bit_length())


def probe_bytes_routed(n: int, k: int, kc: int, nprobe_c: int, gcap: int,
                       d: int, l: int, b: int = 4) -> float:
    """Two-level routed probe HBM traffic: a coarse FlashProbe over the
    ``K_c`` group centroids (top-``nprobe_c``), then a grouped fine
    FlashProbe over only the ``nprobe_c * gcap`` surviving fine centroids
    per query — the flat kernel's ``K * d`` stream shrinks to
    ``K_c * d + n * nprobe_c * gcap * d``."""
    coarse = (n * d + kc * d) * b + 2 * n * nprobe_c * 4
    fine = (n * d + n * nprobe_c * gcap * d) * b + 2 * n * l * 4
    return coarse + fine


def choose_coarse_nprobe(recall_target: float = 0.95) -> int:
    """Coarse probe width for a recall target, from the exponential
    coverage model: with randomly-placed group boundaries the chance the
    true nearest fine centroid's group is outside the ``c`` nearest
    coarse centroids decays like ``exp(-c / 2)``, so
    ``c = ceil(-2 ln(1 - r))`` (r=0.95 -> 6, r=0.99 -> 10)."""
    r = min(max(float(recall_target), 0.0), 1.0 - 1e-9)
    return max(1, int(math.ceil(-2.0 * math.log(1.0 - r))))


def choose_route_params(k: int, nprobe: int, *,
                        recall_target: float = 0.95,
                        hw: Hardware = TPU_V5E) -> tuple[int, int]:
    """Closed-form ``(K_c, nprobe_c)`` for the two-level router.

    Per-query probe cost is ``K_c·d`` (coarse) plus
    ``nprobe_c·(K/K_c)·d`` (fine groups), minimized at
    ``K_c = sqrt(nprobe_c · K)``; ``nprobe_c`` comes from the coverage
    model above. ``K_c`` is rounded to the nearest power of two (so the
    coarse probe tiles cleanly) and clamped to ``[sublane, K // 2]`` —
    below ``2·sublane`` fine cells there is nothing to route and the
    chooser degenerates to flat (``K_c = K`` is never returned)."""
    npc = choose_coarse_nprobe(recall_target)
    if k < 2 * hw.sublane:
        return max(1, k), min(npc, max(1, k))
    ideal = (npc * k) ** 0.5
    kc = 1 << max(0, int(round(math.log2(max(1.0, ideal)))))
    kc = max(hw.sublane, min(kc, k // 2))
    npc = min(npc, kc, max(1, nprobe))
    return kc, npc


def choose_rescore_mult(topk: int, d: int, cand: int, *,
                        recall_target: float = 0.95,
                        code_bytes: float | None = None,
                        full_bytes: float | None = None,
                        hit_rate: float | None = None) -> int:
    """Planner-chosen q8 rescore multiplier from a recall target.

    The propose phase ranks by quantized scores, so the true top-k row
    sits inside the quantized top-``R`` with the same exponential tail
    as the routing coverage model: ``R/topk = ceil(-2 ln(1 - r))``.
    The codec bytes model caps it: rescoring ``R = mult·topk`` rows
    costs ``R·full_bytes`` exact-fp32 traffic per query, which must not
    give back the ``cand·(full_bytes - code_bytes)`` the codec saved on
    the scan — beyond that cap a larger mult is strictly worse than
    scanning fp32 directly. ``code_bytes``/``full_bytes`` default to the
    int8-residual (``d + 4``) and fp32 (``4d``) rows.

    ``hit_rate`` (the device rescore cache's expected hit fraction,
    capacity over live rows) re-prices the rescore row: only cache hits
    stream fresh fp32 bytes from the row pool — misses rescore the
    decoded rows the proposer already materialized — so the effective
    per-row cost is ``max(hit_rate · full_bytes, code_bytes)`` and a
    colder cache admits a deeper proposal list at the same budget."""
    if code_bytes is None:
        code_bytes = d + 4.0
    if full_bytes is None:
        full_bytes = 4.0 * d
    base = choose_coarse_nprobe(recall_target)
    row_bytes = full_bytes
    if hit_rate is not None:
        row_bytes = max(float(code_bytes),
                        full_bytes * min(1.0, max(0.0, float(hit_rate))))
    saved = max(0.0, float(cand) * (full_bytes - code_bytes))
    cap = max(1, int(saved // max(1.0, float(topk) * row_bytes)))
    return max(1, min(base, cap))


def choose_probe_blocks(n: int, k: int, d: int, l: int, *,
                        dtype_bytes: int = 4, hw: Hardware = TPU_V5E
                        ) -> tuple[int, int]:
    """Closed-form (block_n, block_k) for the FlashProbe kernel — the same
    descent as ``choose_blocks``'s FlashAssign leg, with the L-best pool
    charged to the working set. Every selection round sweeps the merged
    ``(B_N, L + B_K)`` pool, so the per-tile selection cost grows as
    ``L·(L + B_K)``: keep B_K moderate when L is large and give the query
    tile the remaining budget (more reuse of the streamed centroid tile).
    """
    budget = vmem_budget(hw)
    l_pad = _round_up(max(1, l), hw.sublane)
    # large L shifts the sweep from MXU matmul to VPU selection rounds;
    # cap B_K so the merged pool stays within a few multiples of B_K.
    bk_cap = 512 if l_pad <= 64 else 256
    bk = _fit_minor(bk_cap, k, hw.lane)
    bn = hw.sublane
    for cand in _CANDIDATE_TILES:
        if cand > _round_up(n, hw.sublane):
            break
        if probe_footprint(cand, bk, l_pad, d, dtype_bytes) <= budget:
            bn = cand
    while (probe_footprint(bn, bk, l_pad, d, dtype_bytes) > budget
           and bk > hw.lane):
        bk //= 2
    while (probe_footprint(bn, bk, l_pad, d, dtype_bytes) > budget
           and bn > hw.sublane):
        bn //= 2
    return bn, bk


def choose_blocks(n: int, k: int, d: int, *, dtype_bytes: int = 4,
                  hw: Hardware = TPU_V5E) -> BlockConfig:
    """Closed-form block selection — zero search, O(#candidates) arithmetic."""
    budget = vmem_budget(hw)

    # --- FlashAssign: the K stream wants large B_K tiles for MXU shape;
    # the resident point tile then takes what is left.
    a_bk = _fit_minor(512, k, hw.lane)
    a_bn = hw.sublane
    for bn in _CANDIDATE_TILES:
        if bn > _round_up(n, hw.sublane):
            break
        if assign_footprint(bn, a_bk, d, dtype_bytes) <= budget:
            a_bn = bn
    while assign_footprint(a_bn, a_bk, d, dtype_bytes) > budget and a_bk > hw.lane:
        a_bk //= 2
    while assign_footprint(a_bn, a_bk, d, dtype_bytes) > budget and a_bn > hw.sublane:
        a_bn //= 2
    # very large d: the centroid tile's rows are sublanes of the (B_K, B_N)
    # score tile, so B_K may go below a lane width as a last resort
    while assign_footprint(a_bn, a_bk, d, dtype_bytes) > budget and a_bk > hw.sublane:
        a_bk //= 2

    # --- Sort-inverse: B_K bounds both the one-hot minor dim and the
    # resident accumulator (bk*d f32); keep it modest, grow the point
    # stream tile (segment locality improves with larger B_N).
    u_bk = _fit_minor(256, k, hw.lane)
    u_bn = hw.sublane
    for bn in _CANDIDATE_TILES:
        if bn > _round_up(n, hw.sublane):
            break
        if update_footprint(bn, u_bk, d, dtype_bytes) <= budget:
            u_bn = bn
    while update_footprint(u_bn, u_bk, d, dtype_bytes) > budget and u_bk > hw.lane:
        u_bk //= 2
    while update_footprint(u_bn, u_bk, d, dtype_bytes) > budget and u_bn > hw.sublane:
        u_bn //= 2

    # --- FlashLloyd (fused): the resident K_pad·d accumulator + centroid
    # block are fixed costs; B_K only sizes the sweep slices, so keep it
    # modest and give the point tile whatever budget remains.
    f_bk = _fit_minor(256, k, hw.lane)
    f_bn = hw.sublane
    k_pad = _round_up(k, f_bk)
    for bn in _CANDIDATE_TILES:
        if bn > _round_up(n, hw.sublane):
            break
        if fused_footprint(bn, f_bk, d, dtype_bytes, k_pad) <= budget:
            f_bn = bn
    while (fused_footprint(f_bn, f_bk, d, dtype_bytes, k_pad) > budget
           and f_bk > hw.lane):
        f_bk //= 2
        k_pad = _round_up(k, f_bk)
    while (fused_footprint(f_bn, f_bk, d, dtype_bytes, k_pad) > budget
           and f_bn > hw.sublane):
        f_bn //= 2

    return BlockConfig(assign_block_n=a_bn, assign_block_k=a_bk,
                       update_block_n=u_bn, update_block_k=u_bk,
                       fused_block_n=f_bn, fused_block_k=f_bk)
