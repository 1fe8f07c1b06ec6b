"""FlashIVF — an online IVF (inverted-file) vector-search index built
entirely from flash-kmeans primitives.

The index is the canonical downstream consumer of k-means centroids
(FAISS-style coarse quantization), and every stage maps onto a piece
this repo already has:

- **train**  — coarse centroids come from the existing drivers: the
  in-core ``KMeans`` fit, or ``ChunkedKMeans`` when the corpus is an
  out-of-core host array / chunk factory;
- **invert** — posting lists are the *sort-inverse mapping itself*: one
  stable ``argsort`` of the assignment vector is the concatenation of
  all posting lists, and ``searchsorted`` of the sorted assignments
  yields the CSR offsets — zero per-point scatters, the same dataflow
  trick as ``kernels/sort_inverse_update.py`` (see DESIGN.md,
  "FlashIVF dataflow");
- **probe** — ``ops.flash_probe`` (fused distance + online top-L) picks
  the ``nprobe`` nearest coarse cells per query;
- **scan** — the flat fp32 search inverts the unit's (query, probe)
  pairs into per-list query groups (the sort-inverse mapping once more:
  a stable argsort by list id, runs cut into segments of ``G`` queries)
  and ``ops.flash_scan_lists`` streams each probed list from the store
  once per group, in place, through scalar-prefetched block indices; a
  per-query top-k over the ``nprobe`` per-list results finishes it. No
  ``(B, nprobe·width, d)`` candidate copy is written. The routed,
  quantized, rescore and sharded searches still gather candidates and
  scan them with the grouped variant ``ops.flash_probe_grouped``. The
  score matrix never exists in HBM at any stage;
- **online** — ``add`` assigns new vectors with FlashAssign, appends
  them to their lists in CSR batch order, and folds their sufficient
  statistics into the running per-cluster ``SufficientStats``
  (core.streaming); a periodic ``refresh`` commits the pending evidence
  and re-centers the coarse centroids via the warm-start
  ``finalize`` M-step — one O(K·d) reduction, never a refit.

Storage layout: posting-list payloads live behind ``index/store.py``
(``BucketStore``) — the index never touches a raw bucket tensor. The
``padded`` backend is the historical capacity-padded ``(K, cap, d)``
tensor; the ``paged`` backend is a PagedAttention-style flat pool of
fixed-size pages with per-cell page tables, a free-list allocator, and
LRU eviction under a byte budget (resident memory ~ occupied pages, not
``K * max_cell_cap``). Padded slots in either layout hold a large finite
sentinel coordinate so their distances are astronomically large but
never NaN/inf inside the kernel's crossterm — they can only surface when
a query probes fewer valid candidates than ``topk``, in which case the
returned id is an honest ``-1``. The list-major scan reads only each
list's ``counts`` rows and reports such a slot the same way. Search
gathers and the scan's tile count are capped at the store's *occupied*
width (``gather_width``, a power-of-two bucket), so the work — and the
plan-cache key — track occupancy instead of physical capacity.

**Sharded FlashIVF** (``pctx`` — a ``core.parallel.ParallelContext``):
cells are partitioned over the mesh's ``cells`` axis — each shard owns
``K / P_k`` centroids *and their posting lists* — and the whole search
runs inside one shard_map'd program:

  local ``flash_probe`` over owned centroids  ->  cross-shard top-L
  merge (O(b·L) bytes)  ->  local grouped scan of the *owned* probed
  cells' buckets  ->  global top-k merge (O(b·topk) bytes).

Posting-list payloads never cross shards; the only wire traffic is the
two (value, index) list merges. ``build`` trains through the same
context (data-parallel and/or two-stage K-sharded Lloyd), and
``add``/``refresh`` route the pending ``SufficientStats`` through the
same O(K·d) psum tree as every other driver.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import heuristics as _heur
from repro.core import plan as _plan
from repro.core.chunked import ChunkedKMeans
from repro.core.init import init_centroids
from repro.core.kmeans import KMeans, KMeansConfig
from repro.core.streaming import SufficientStats
from repro.index import rescore_cache as _rcache
from repro.index import router as _router
from repro.index import store as _store
from repro.kernels import ops, ref
from repro.reliability.faults import InjectedFault, corrupt_stats

Array = jax.Array

# Padded-slot coordinate (see index/store.py, the storage layer).
_PAD_COORD = _store._PAD_COORD


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def csr_from_assignments(a: Array, k: int) -> tuple[Array, Array]:
    """CSR posting lists from an assignment vector — the sort-inverse path.

    ``order`` (N,) is the stable argsort of ``a``: the concatenation of
    all posting lists (cluster-major, original order within a cluster).
    ``offsets`` (K+1,) are the segment boundaries: list ``j`` is
    ``order[offsets[j]:offsets[j+1]]``. The inverse mapping *is* the
    index — no per-point scatter is ever issued.
    """
    order = jnp.argsort(a).astype(jnp.int32)
    a_sorted = jnp.take(a, order)
    offsets = jnp.searchsorted(a_sorted, jnp.arange(k + 1, dtype=a.dtype)
                               ).astype(jnp.int32)
    return order, offsets


def recall_at_k(ids, ids_ref) -> float:
    """Mean fraction of reference neighbours retrieved, per query.

    ``ids``/``ids_ref``: (B, topk) id arrays (brute-force order as the
    reference); unfilled ``-1`` slots count as misses. The one recall
    definition shared by the serve launcher and the index benchmark.
    """
    ids, ids_ref = np.asarray(ids), np.asarray(ids_ref)
    k = ids_ref.shape[1]
    return float(np.mean([
        len(set(a.tolist()) & set(b.tolist()) - {-1}) / k
        for a, b in zip(ids, ids_ref)]))


def _train_sharded(pctx, cfg: KMeansConfig, key, x: Array
                   ) -> tuple[Array, Array, Array]:
    """Distributed build-time training: the ParallelContext Lloyd loop
    (one O(K·d) psum per iteration; two-stage argmin under K-sharding)
    followed by one two-stage assignment pass under the final centroids
    — the same per-shard dataflow the online ``add`` path uses. Ragged
    N is padded to a data-shard multiple and masked out of the
    statistics. Returns ``(centroids, assignments, min_sq_dists)``."""
    n = x.shape[0]
    c0 = init_centroids(key, x, cfg.k, cfg.init)
    x_pad, mask, _ = pctx.pad_points(x)
    ragged = x_pad.shape[0] != n
    fit = pctx.make_kmeans_fit(cfg, masked=ragged)
    xs = pctx.shard_points(x_pad)
    c0s = pctx.shard_centroids(c0)
    if ragged:
        c = fit(xs, pctx.put(mask, P(pctx.data_axes)), c0s).centroids
    else:
        c = fit(xs, c0s).centroids
    a, m = pctx.make_assign(cfg)(xs, c)
    return c, a[:n], m[:n]


def _list_segments(probe: Array, g: int, k: int
                  ) -> tuple[Array, Array, Array, Array]:
    """Invert a unit's ``(B, nprobe)`` probe lists into per-list query
    groups — the sort-inverse mapping applied to the scan.

    The ``B·nprobe`` (query, probe) pairs are stably sorted by list id
    and each list's run is cut into segments of at most ``g`` queries.
    The segment count is static, ``ceil(B·nprobe/g) + min(k, B·nprobe)``
    (every list run adds at most one partial segment); segments past the
    real ones are padding. Returns ``(seg_list (S,), n_seg, qrow (S·g,),
    slot (B·nprobe,))``: each segment's list id (0 on padding), the
    number of real segments, the query each group row holds (query 0 on
    padding rows, whose results are never read) and, per pair in
    query-major order, its flat (segment, row) position.
    """
    b, nprobe = probe.shape
    p = b * nprobe
    s_max = -(-p // g) + min(k, p)
    flat = probe.reshape(p)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    lists = flat[order]
    pos = jnp.arange(p, dtype=jnp.int32)
    new_run = jnp.concatenate([jnp.ones((1,), bool), lists[1:] != lists[:-1]])
    in_run = pos - jax.lax.cummax(jnp.where(new_run, pos, 0))
    seg = jnp.cumsum((in_run % g == 0).astype(jnp.int32)) - 1
    row = seg * g + in_run % g
    n_seg = seg[-1] + 1
    seg_list = jnp.zeros((s_max,), jnp.int32).at[seg].set(lists)
    qrow = jnp.zeros((s_max * g,), jnp.int32).at[row].set(order // nprobe)
    slot = jnp.zeros((p,), jnp.int32).at[order].set(row)
    return seg_list, n_seg, qrow, slot


def _scan_lists(q: Array, probe: Array, counts: Array, store_arrays: tuple,
                *, kind: str, topk: int, width: int, ps: int, nsh: int,
                g: int, bw: int, interpret: bool | None
                ) -> tuple[Array, Array]:
    """List-major scan of a flat fp32 store: every probed list streams
    from the store once per query group (``flash_scan_lists``) instead of
    once per query through a gathered ``(B, nprobe·width, d)`` copy.

    Each query then keeps its best ``topk`` of its ``nprobe`` per-list
    results, in probe-rank order, so an exact tie goes to the lower
    (rank, slot) position — the candidate-axis order of the gathered
    block. Where a query's lists hold fewer than ``topk`` rows the slot
    is id -1 at a padding row's (finite, astronomically large) distance.
    """
    b, nprobe = probe.shape
    k = counts.shape[0]
    with jax.named_scope("ivf.gather"):
        seg_list, n_seg, qrow, slot = _list_segments(probe, g, k)
        s_max = seg_list.shape[0]
        seg_count = jnp.where(jnp.arange(s_max) < n_seg, counts[seg_list], 0)
        qg = jnp.take(q, qrow, axis=0).reshape(s_max, g, q.shape[1])
        payload, blocks, bw = _store.list_blocks(
            kind, store_arrays, seg_list, bw, width, ps, nsh)
    with jax.named_scope("ivf.scan"):
        slots, score = ops.flash_scan_lists(qg, payload, seg_count, blocks,
                                            l=topk, block_w=bw,
                                            interpret=interpret)
        l = score.shape[-1]
        score = score.reshape(s_max * g, l)[slot].reshape(b, nprobe * l)
        slots = slots.reshape(s_max * g, l)[slot].reshape(b, nprobe * l)
        neg, pick = jax.lax.top_k(-score, topk)       # ties: lower position
        lst = jnp.take_along_axis(probe, pick // l, axis=1)
        ids = _store.slot_ids(kind, store_arrays, lst,
                              jnp.take_along_axis(slots, pick, axis=1),
                              ps, nsh)
        filled = jnp.isfinite(neg)
        q32 = q.astype(jnp.float32)
        dist = jnp.maximum(
            jnp.sum(q32 * q32, axis=-1, keepdims=True) - neg, 0.0)
        pad = jnp.sum(jnp.square(q32 - _PAD_COORD), axis=-1, keepdims=True)
        return jnp.where(filled, ids, -1), jnp.where(filled, dist, pad)


@functools.partial(jax.jit, static_argnames=("kind", "topk", "nprobe",
                                             "width", "ps", "nsh", "bqn",
                                             "bqk", "g", "bw", "interpret"))
def _ivf_search(q: Array, centroids: Array, c_sq: Array, counts: Array,
                store_arrays: tuple, *,
                kind: str, topk: int, nprobe: int, width: int, ps: int,
                nsh: int, bqn: int, bqk: int, g: int, bw: int,
                interpret: bool | None) -> tuple[Array, Array]:
    """Batched two-stage IVF search, fully fused (one jit per geometry).

    Stage 1: FlashProbe over the coarse centroids -> (B, nprobe) cells
    (``c_sq`` is the index's cached ``||c||^2`` strip — no per-call
    norm recompute).
    Stage 2: the list-major scan (``_scan_lists``): the unit's (query,
    probe) pairs inverted into per-list query groups, each probed list
    read in place from the store (padded tiles or pages, up to its
    ``counts`` rows) once per group, then a per-query top-k merge.
    """
    with jax.named_scope("ivf.probe"):
        probe, _ = ops.flash_probe(q, centroids.astype(q.dtype), l=nprobe,
                                   block_n=bqn, block_k=bqk,
                                   interpret=interpret, want_dists=False,
                                   c_sq=c_sq)
    return _scan_lists(q, probe, counts, store_arrays, kind=kind, topk=topk,
                       width=width, ps=ps, nsh=nsh, g=g, bw=bw,
                       interpret=interpret)


def _route_cells(q: Array, centroids: Array, coarse: Array,
                 coarse_sq: Array, members: Array, *, k: int, nprobe: int,
                 npc: int, leff: int, bcn: int, bck: int, bfb: int,
                 bfc: int, interpret: bool | None) -> Array:
    """Two-level cell selection (the TwoLevelRouter's jit-side half).

    Coarse FlashProbe picks the ``npc`` nearest of the K_c group
    centroids; the member table expands them into each query's candidate
    fine cells (sentinel ``K`` on thin-group padding, which gathers the
    ``_PAD_COORD`` row and can never win); a grouped FlashProbe over
    only those ``npc * gcap`` fine centroids yields the probe list —
    the flat kernel's ``O(K·d)`` stream never happens. Returns
    ``(B, nprobe)`` cells in ``[0, K]`` (``K`` = no cell), ascending by
    distance — at ``leff < nprobe`` the tail is sentinel-padded.
    """
    b, d = q.shape
    gcap = members.shape[1]
    cidx, _ = ops.flash_probe(q, coarse.astype(q.dtype), l=npc,
                              block_n=bcn, block_k=bck,
                              interpret=interpret, want_dists=False,
                              c_sq=coarse_sq)
    cand_cells = jnp.take(members, cidx, axis=0).reshape(b, npc * gcap)
    cpad = jnp.concatenate(
        [centroids.astype(q.dtype),
         jnp.full((1, d), _PAD_COORD, q.dtype)], axis=0)
    cand_c = jnp.take(cpad, cand_cells, axis=0)      # (B, npc*gcap, d)
    li, _ = ops.flash_probe_grouped(q, cand_c, l=leff, block_b=bfb,
                                    block_c=bfc, interpret=interpret,
                                    want_dists=False)
    cells = jnp.take_along_axis(cand_cells, li, axis=1)
    if leff < nprobe:
        cells = jnp.pad(cells, ((0, 0), (0, nprobe - leff)),
                        constant_values=k)
    return cells


@functools.partial(jax.jit, static_argnames=("kind", "k", "topk", "nprobe",
                                             "npc", "leff", "width", "ps",
                                             "nsh", "bcn", "bck", "bfb",
                                             "bfc", "bsb", "bsc",
                                             "interpret"))
def _ivf_search_routed(q: Array, centroids: Array, coarse: Array,
                       coarse_sq: Array, members: Array,
                       store_arrays: tuple, *, kind: str, k: int,
                       topk: int, nprobe: int, npc: int, leff: int,
                       width: int, ps: int, nsh: int, bcn: int, bck: int,
                       bfb: int, bfc: int, bsb: int, bsc: int,
                       interpret: bool | None) -> tuple[Array, Array]:
    """Routed two-stage search: ``_route_cells`` replaces the flat probe;
    the bucket gather/scan is byte-identical to ``_ivf_search`` except
    that sentinel cells (``probe == K``) are clamped for the gather and
    masked back to padding rows / ``-1`` ids afterwards."""
    with jax.named_scope("ivf.probe"):
        probe = _route_cells(q, centroids, coarse, coarse_sq, members, k=k,
                             nprobe=nprobe, npc=npc, leff=leff, bcn=bcn,
                             bck=bck, bfb=bfb, bfc=bfc, interpret=interpret)
    with jax.named_scope("ivf.gather"):
        safe = jnp.minimum(probe, k - 1)
        cand_x, cand_ids = _store.gather_global(kind, store_arrays, safe,
                                                width, ps, nsh)
        pad = jnp.repeat(probe >= k, width, axis=1)  # (B, nprobe*width)
        cand_x = jnp.where(pad[:, :, None],
                           jnp.asarray(_PAD_COORD, cand_x.dtype), cand_x)
        cand_ids = jnp.where(pad, -1, cand_ids)
    with jax.named_scope("ivf.scan"):
        li, dist = ops.flash_probe_grouped(q, cand_x, l=topk,
                                           block_b=bsb, block_c=bsc,
                                           interpret=interpret)
        ids = jnp.take_along_axis(cand_ids, li, axis=1)
    return ids, dist


def _route_cells_sharded(pctx, ka, q, c_local, coarse, coarse_sq, members,
                         *, k, k_local, nprobe, npc, leff, bcn, bck,
                         bfb, bfc, alive, interpret):
    """Two-level cell selection under cells sharding.

    The coarse probe runs on replicated state (coarse centroids +
    member table), so every K-shard computes the identical candidate
    cell list with zero wire traffic; each shard then scores only the
    candidate fine centroids *it owns* (non-owned slots gather the
    local padding row) and the probe list comes out of the same
    cross-shard top-L merge the flat sharded path uses. The tie key is
    the candidate-axis position — the exact order the single-device
    ``_route_cells`` scan sees — so the merged probe list matches the
    single-device routed list entry for entry.
    """
    bl, d = q.shape
    gcap = members.shape[1]
    cidx, _ = ops.flash_probe(q, coarse.astype(q.dtype), l=npc,
                              block_n=bcn, block_k=bck,
                              interpret=interpret, want_dists=False,
                              c_sq=coarse_sq)
    cand_cells = jnp.take(members, cidx, axis=0).reshape(bl, npc * gcap)
    lo = jax.lax.axis_index(ka) * k_local
    relc = cand_cells - lo
    ownc = jnp.logical_and(relc >= 0, relc < k_local)
    cpad = jnp.concatenate(
        [c_local.astype(q.dtype),
         jnp.full((1, d), _PAD_COORD, q.dtype)], axis=0)
    cand_c = jnp.take(cpad, jnp.where(ownc, relc, k_local), axis=0)
    fli, flv = ops.flash_probe_grouped(q, cand_c, l=leff, block_b=bfb,
                                       block_c=bfc, interpret=interpret,
                                       want_dists=False)
    fcell = jnp.take_along_axis(jnp.where(ownc, cand_cells, k), fli,
                                axis=1)
    gcell, _ = pctx.merge_topl(fcell, flv, nprobe, tie=fli, valid=alive)
    return gcell


def _q8_propose_routed(q: Array, centroids: Array, coarse: Array,
                       coarse_sq: Array, members: Array,
                       store_arrays: tuple, *, kind: str, k: int,
                       r: int, nprobe: int, npc: int, leff: int,
                       width: int, ps: int, nsh: int, bcn: int,
                       bck: int, bfb: int, bfc: int, bsb: int,
                       bsw: int, interpret: bool | None
                       ) -> tuple[Array, Array]:
    """Routed phase-1 proposer body on a quantized store:
    ``_route_cells`` in the probe seat; sentinel cells gather at a
    clamped index and mask out through scale 0.0 / id -1 (the same
    contract thin cells already use), so the rescore phase needs no
    change. Plain traceable function — jitted standalone (host-rescore
    oracle) and fused with cache lookup + rescore (device path)."""
    with jax.named_scope("ivf.probe"):
        probe = _route_cells(q, centroids, coarse, coarse_sq, members, k=k,
                             nprobe=nprobe, npc=npc, leff=leff, bcn=bcn,
                             bck=bck, bfb=bfb, bfc=bfc, interpret=interpret)
    *arrays, anchors = store_arrays
    with jax.named_scope("ivf.gather"):
        safe = jnp.minimum(probe, k - 1)
        codes, scales, cand_ids = _store.gather_global_q8(
            kind, tuple(arrays), safe, width, ps, nsh)
        pad = probe >= k                                 # (B, nprobe)
        padw = jnp.repeat(pad, width, axis=1)
        scales = jnp.where(padw, 0.0, scales)
        cand_ids = jnp.where(padw, -1, cand_ids)
    b, d = q.shape
    with jax.named_scope("ivf.scan"):
        anch = jnp.take(anchors, safe, axis=0)           # (B, nprobe, d)
        anch = jnp.where(pad[:, :, None], 0.0, anch)
        qp = q.astype(jnp.float32)[:, None, :] - anch
        li, val = ops.flash_probe_grouped_q8(
            qp, codes.reshape(b, nprobe, width, d),
            scales.reshape(b, nprobe, width), l=r,
            block_b=bsb, block_w=bsw, interpret=interpret)
        ids = jnp.where(jnp.isfinite(val),
                        jnp.take_along_axis(cand_ids, li, axis=1), -1)
        deq = (jnp.take_along_axis(anch, (li // width)[:, :, None], axis=1)
               + jnp.take_along_axis(codes, li[:, :, None], axis=1
                                     ).astype(jnp.float32)
               * jnp.take_along_axis(scales, li, axis=1)[:, :, None])
    return ids, deq


_ivf_search_q8_routed = functools.partial(
    jax.jit, static_argnames=("kind", "k", "r", "nprobe", "npc", "leff",
                              "width", "ps", "nsh", "bcn", "bck", "bfb",
                              "bfc", "bsb", "bsw", "interpret")
)(_q8_propose_routed)


def _q8_propose(q: Array, centroids: Array, c_sq: Array,
                store_arrays: tuple, *,
                kind: str, r: int, nprobe: int, width: int, ps: int,
                nsh: int, bqn: int, bqk: int, bsb: int, bsw: int,
                interpret: bool | None) -> tuple[Array, Array]:
    """Phase 1 of two-phase search on a quantized store: the cheap
    proposer. Probe as usual, gather int8 codes + scales instead of f32
    rows, and scan in the residual frame — ``q' = q - anchor[cell]``
    makes the kernel's ``||q' - r||^2`` the *true* quantized distance
    (globally comparable across probe slots, no per-candidate anchor
    gather). Returns the top-``r`` candidate ids (-1 where fewer than
    ``r`` live candidates exist) and their dequantized f32 rows — the
    rescore fallback for ids the rescore tier no longer holds. Plain
    traceable body; jitted standalone below and fused on the device
    path.
    """
    with jax.named_scope("ivf.probe"):
        probe, _ = ops.flash_probe(q, centroids.astype(q.dtype), l=nprobe,
                                   block_n=bqn, block_k=bqk,
                                   interpret=interpret, want_dists=False,
                                   c_sq=c_sq)
    *arrays, anchors = store_arrays
    with jax.named_scope("ivf.gather"):
        codes, scales, cand_ids = _store.gather_global_q8(
            kind, tuple(arrays), probe, width, ps, nsh)
    b, d = q.shape
    with jax.named_scope("ivf.scan"):
        anch = jnp.take(anchors, probe, axis=0)      # (B, nprobe, d)
        qp = q.astype(jnp.float32)[:, None, :] - anch
        li, val = ops.flash_probe_grouped_q8(
            qp, codes.reshape(b, nprobe, width, d),
            scales.reshape(b, nprobe, width), l=r,
            block_b=bsb, block_w=bsw, interpret=interpret)   # (B, r)
        ids = jnp.where(jnp.isfinite(val),
                        jnp.take_along_axis(cand_ids, li, axis=1), -1)
        deq = (jnp.take_along_axis(anch, (li // width)[:, :, None], axis=1)
               + jnp.take_along_axis(codes, li[:, :, None], axis=1
                                     ).astype(jnp.float32)
               * jnp.take_along_axis(scales, li, axis=1)[:, :, None])
    return ids, deq


_ivf_search_q8 = functools.partial(
    jax.jit, static_argnames=("kind", "r", "nprobe", "width", "ps", "nsh",
                              "bqn", "bqk", "bsb", "bsw", "interpret")
)(_q8_propose)


def _rescore_body(q: Array, cand: Array, ids: Array, res_rows: Array,
                  found: Array, *, topk: int, bsb: int, bsc: int,
                  interpret: bool | None) -> tuple[Array, Array]:
    """Phase 2: exact verify. Score the ``r`` proposed rows at full
    precision — the rescore tier's original rows where resident, the
    dequantized codes otherwise (same overlay ``dense()`` applies, so
    two-phase and brute-force score literally identical rows) — and
    keep the true top-k. Dead proposals (id -1) become padding rows."""
    with jax.named_scope("ivf.rescore"):
        cand = jnp.where(found[:, :, None], res_rows, cand)
        cand = jnp.where((ids < 0)[:, :, None], _PAD_COORD, cand)
        li, dist = ops.flash_probe_grouped(q.astype(cand.dtype), cand,
                                           l=topk, block_b=bsb, block_c=bsc,
                                           interpret=interpret)
        return jnp.take_along_axis(ids, li, axis=1), dist


_ivf_rescore = functools.partial(
    jax.jit, static_argnames=("topk", "bsb", "bsc", "interpret")
)(_rescore_body)


@functools.partial(jax.jit, static_argnames=("kind", "r", "nprobe",
                                             "width", "ps", "nsh", "bqn",
                                             "bqk", "bsb", "bsw",
                                             "interpret"))
def _ivf_search_q8_device(q: Array, centroids: Array, c_sq: Array,
                          store_arrays: tuple, ckeys: Array, crows: Array,
                          *, kind: str, r: int, nprobe: int, width: int,
                          ps: int, nsh: int, bqn: int, bqk: int, bsb: int,
                          bsw: int, interpret: bool | None
                          ) -> tuple[Array, Array, Array, Array]:
    """The device-resident hot path, phase 1: propose + cache lookup in
    one jitted program — phase 2 reads original rows from the
    ``DeviceRescoreCache`` gather instead of a host reservoir
    round-trip, so no device→host transfer happens anywhere. The exact
    rescore itself runs through the *same* ``_ivf_rescore`` executable
    the host oracle uses (deliberately not fused here: sharing the
    compiled program is what makes device-vs-host distances bitwise
    identical, not merely close — fusing the scan changes XLA's
    contraction order at the ulp level)."""
    ids, deq = _q8_propose(q, centroids, c_sq, store_arrays, kind=kind,
                           r=r, nprobe=nprobe, width=width, ps=ps,
                           nsh=nsh, bqn=bqn, bqk=bqk, bsb=bsb, bsw=bsw,
                           interpret=interpret)
    with jax.named_scope("ivf.rescore"):
        rows, found = _rcache.cache_lookup(ckeys, crows, ids)
    return ids, deq, rows, found


@functools.partial(jax.jit, static_argnames=("kind", "k", "r", "nprobe",
                                             "npc", "leff", "width", "ps",
                                             "nsh", "bcn", "bck", "bfb",
                                             "bfc", "bsb", "bsw",
                                             "interpret"))
def _ivf_search_q8_routed_device(q: Array, centroids: Array,
                                 coarse: Array, coarse_sq: Array,
                                 members: Array, store_arrays: tuple,
                                 ckeys: Array, crows: Array, *, kind: str,
                                 k: int, r: int, nprobe: int, npc: int,
                                 leff: int, width: int, ps: int, nsh: int,
                                 bcn: int, bck: int, bfb: int, bfc: int,
                                 bsb: int, bsw: int, interpret: bool | None
                                 ) -> tuple[Array, Array, Array, Array]:
    """Routed variant of the device-resident hot path (phase 1)."""
    ids, deq = _q8_propose_routed(
        q, centroids, coarse, coarse_sq, members, store_arrays, kind=kind,
        k=k, r=r, nprobe=nprobe, npc=npc, leff=leff, width=width, ps=ps,
        nsh=nsh, bcn=bcn, bck=bck, bfb=bfb, bfc=bfc, bsb=bsb, bsw=bsw,
        interpret=interpret)
    with jax.named_scope("ivf.rescore"):
        rows, found = _rcache.cache_lookup(ckeys, crows, ids)
    return ids, deq, rows, found


class IVFIndex:
    """Online IVF index: coarse k-means cells + CSR posting lists.

    >>> index = IVFIndex.build(x, k=256, max_iters=10)
    >>> ids, dists = index.search(q, topk=10, nprobe=16)
    >>> index.add(x_new)                 # FlashAssign + list append
    >>> index.refresh()                  # warm-start re-center, O(K d)
    >>> ids_ref, _ = index.search_brute(q, topk=10)   # exactness oracle

    ``store`` selects the posting-list backend ("padded" | "paged",
    default from ``REPRO_BUCKET_STORE``); an already-built
    ``BucketStore`` instance is also accepted. ``codec`` selects the
    payload codec ("fp32" | "q8", default from ``REPRO_BUCKET_CODEC``)
    — orthogonal to the backend axis: a "q8" index wraps either backend
    in a ``QuantizedBucketStore`` (anchored at the build-time
    centroids) and searches in two phases (quantized top-R proposal,
    exact fp32 rescore; ``R = rescore_mult * topk``, or the codec-aware
    recall-target chooser with ``rescore_mult="auto"``).
    ``rescore_bytes`` budgets the full-precision rescore reservoir
    (None = unbounded). ``rescore`` picks the phase-2 row source
    ("device" | "host", default from ``REPRO_RESCORE``, else "device"):
    "device" fuses propose → ``DeviceRescoreCache`` lookup → exact
    rescore into one jitted program with zero host transfers per call;
    "host" keeps the reservoir round trip — the parity oracle.

    ``router`` selects how search picks its ``nprobe`` cells ("flat" |
    "two_level", default from ``REPRO_ROUTER``; an existing Router
    instance is also accepted — see ``index/router.py``): "two_level"
    trains a coarse k-means over the K centroids and probes
    hierarchically, dropping per-query probe cost from ``O(K·d)`` to
    ``O(K_c·d + nprobe_c·gcap·d)``. Orthogonal to both store axes.
    """

    def __init__(self, centroids: Array, capacity: int, *,
                 max_cap: int | None = None,
                 interpret: bool | None = None,
                 planner: "_plan.KernelPlanner | None" = None,
                 pctx=None, store: "str | _store.BucketStore | None" = None,
                 page_size: int | None = None,
                 store_bytes: int | None = None,
                 codec: str | None = None, rescore_mult: "int | str" = 4,
                 rescore_bytes: int | None = None,
                 rescore: str | None = None,
                 router=None):
        k, d = centroids.shape
        self.centroids = centroids
        self.k, self.d = k, d
        self.interpret = interpret
        self.pctx = pctx
        if isinstance(rescore_mult, str):
            if rescore_mult != "auto":
                raise ValueError(f"rescore_mult={rescore_mult!r}: "
                                 f"expected an int or 'auto'")
            self.rescore_mult = None   # chosen per geometry (_rescore_r)
        else:
            self.rescore_mult = max(1, int(rescore_mult))
        n_shards = 1
        if pctx is not None and pctx.k_axis is not None:
            pctx.k_local(k)   # raises unless K divides the cells axis
            n_shards = pctx.n_k_shards
        if isinstance(store, _store.BucketStore):
            self.store = store
        else:
            from repro.index.quant import default_codec_kind
            codec = default_codec_kind() if codec is None else codec
            if codec == "fp32":
                self.store = _store.make_store(
                    store, k, d, centroids.dtype, capacity=int(capacity),
                    max_cap=max_cap, page_size=page_size,
                    max_bytes=store_bytes, n_shards=n_shards)
            else:
                # quantized payloads are anchored at the *build-time*
                # centroids: refresh() moves the routing centroids only,
                # so stored codes stay decodable without re-encoding
                self.store = _store.make_quantized_store(
                    store, k, d, centroids.dtype, anchors=centroids,
                    codec=codec, capacity=int(capacity), max_cap=max_cap,
                    page_size=page_size, max_bytes=store_bytes,
                    n_shards=n_shards, rescore_bytes=rescore_bytes,
                    rescore=rescore)
        self.n_total = 0
        # reliability state: the optional fault injector and repair
        # counters (spill/evict accounting lives in the store)
        self.faults = None          # a reliability.faults.FaultInjector
        self.repaired_cells = 0     # NaN stats rows zeroed by refresh
        self.reseeded_cells = 0     # dead cells re-seeded by refresh
        # committed evidence (what the current centroids were refreshed
        # from) and pending evidence (folded in by the next refresh)
        self.stats = SufficientStats.zero(k, d)
        self._pending = SufficientStats.zero(k, d)
        # all block shapes come from the planner, per *observed* shape
        # bucket — assignment blocks at each add batch's size, search
        # blocks once per query geometry (cached below; repeated traffic
        # is a pure cache hit, zero chooser calls). Under a k-sharded
        # pctx every plan is taken at the *per-shard* shapes (K/P_k
        # centroids, the owned candidate block), not the global ones.
        self.planner = planner if planner is not None \
            else _plan.default_planner()
        # per-centroid-set ||c||^2 cache (fed to every flash_probe as
        # c_sq); refresh() — the only path that moves centroids —
        # invalidates it
        self._cnorms: Array | None = None
        # the Router decides which cells a query probes; flat == the
        # historical single-level FlashProbe (see index/router.py)
        self.router = _router.make_router(router, centroids,
                                          planner=self.planner,
                                          interpret=interpret)
        self._search_plans: dict[tuple, tuple[int, int, int, int]] = {}
        self._sharded_search: dict[tuple, object] = {}
        self._add_programs: dict[int, object] = {}
        # hoisted all-shards-alive mask: the healthy sharded path reuses
        # one device constant instead of rebuilding np.ones + uploading
        # it per query batch
        self._shard_ok_dev: Array | None = None
        self._place()

    # ------------------------------------------------------------------
    # store views (the only raw-tensor access path is index/store.py)
    # ------------------------------------------------------------------

    @property
    def dtype(self):
        return self.store.dtype

    @property
    def cap(self) -> int:
        """Physical slots per cell (padded: ``cap``; paged: table width
        in pages times the page size)."""
        return self.store.capacity

    @property
    def max_cap(self) -> int | None:
        return self.store.max_cap

    @property
    def counts(self) -> Array:
        return self.store.counts

    @counts.setter
    def counts(self, v) -> None:
        self.store.set_counts(v)

    @property
    def spilled(self) -> int:
        return self.store.spilled

    @spilled.setter
    def spilled(self, v) -> None:
        self.store.spilled = int(v)

    @property
    def spill_counts(self) -> np.ndarray:
        return self.store.spill_counts

    @spill_counts.setter
    def spill_counts(self, v) -> None:
        self.store.spill_counts = np.asarray(v, np.int64)

    @property
    def evicted(self) -> int:
        return self.store.evicted

    @property
    def evict_counts(self) -> np.ndarray:
        return self.store.evict_counts

    @property
    def store_kind(self) -> str:
        return self.store.kind

    @property
    def codec_kind(self) -> str:
        """Payload codec of the posting-list store ("fp32" | "q8")."""
        return self.store.codec_kind

    def resident_bytes(self) -> int:
        """Device bytes held by the posting-list payload (+ tables)."""
        return self.store.resident_bytes()

    def block_until_ready(self) -> None:
        self.store.block_until_ready()

    # ------------------------------------------------------------------
    # sharding plumbing (no-ops without a k-sharded ParallelContext)
    # ------------------------------------------------------------------

    @property
    def _k_sharded(self) -> bool:
        return self.pctx is not None and self.pctx.k_axis is not None

    def _shard_cfg(self) -> KMeansConfig:
        """The config the sharded assign/stats programs plan with."""
        return KMeansConfig(k=self.k, interpret=self.interpret,
                            planner=self.planner)

    def _place(self) -> None:
        """Pin the index state onto the mesh: each shard owns K/P_k
        cells — centroids, the store's payload (padded buckets, or the
        page pool + tables), counts and the running ``SufficientStats``
        slices all partitioned over the cells axis. Host-side mutations
        (append / grow / refresh) call this again so placement survives
        functional updates."""
        if not self._k_sharded:
            return
        pctx, ka = self.pctx, self.pctx.k_axis
        self.centroids = pctx.put(self.centroids, P(ka, None))
        self.store.place(pctx)
        place = lambda st: SufficientStats(
            pctx.put(st.sums, P(ka, None)), pctx.put(st.counts, P(ka)),
            st.inertia)
        self.stats = place(self.stats)
        self._pending = place(self._pending)
        if self._cnorms is not None:
            self._cnorms = pctx.put(self._cnorms, P(ka))
        if self.router.kind == "two_level":
            # coarse level replicates (it is K_c·d + K_c·gcap ints —
            # tiny next to the partitioned posting lists); the fine
            # level keeps the cells-axis partition
            rt = self.router
            rt.coarse = pctx.put(rt.coarse, P(None, None))
            rt.coarse_sq = pctx.put(rt.coarse_sq, P(None))
            rt.members = pctx.put(rt.members, P(None, None))

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def build(cls, x, k: int, *, max_iters: int = 10, init: str = "kmeans++",
              tol: float = 0.0, step_impl: str = "auto",
              capacity: int | None = None, max_cap: int | None = None,
              chunk_size: int | None = None,
              seed: int = 0, interpret: bool | None = None,
              planner: "_plan.KernelPlanner | None" = None,
              pctx=None, store: "str | None" = None,
              page_size: int | None = None,
              store_bytes: int | None = None,
              codec: str | None = None, rescore_mult: "int | str" = 4,
              rescore_bytes: int | None = None,
              rescore: str | None = None,
              router=None) -> "IVFIndex":
        """Train coarse centroids and invert the corpus into posting lists.

        ``x``: (N, d) array — or, with ``chunk_size`` set, a host numpy
        array / chunk factory handled out-of-core by ``ChunkedKMeans``
        (training *and* inversion then stream in chunks; device memory
        stays O(chunk + K·cap·d)).

        ``pctx``: train and serve on a mesh — points sharded over the
        data axes (one O(K·d) psum per Lloyd iteration, the same
        ``tol`` early-stop rule as single-device), cells (and their
        posting lists) partitioned over the cells axis, and the
        build-time assignment computed by the same two-stage argmin the
        sharded search uses. A ragged N is padded to a shard multiple
        and masked out of the statistics. With ``chunk_size`` set the
        *training* stays the single-device out-of-core ``ChunkedKMeans``
        loop (the corpus doesn't fit on the mesh by assumption); the
        mesh applies to everything after it — the per-chunk ``add``
        inversion passes, placement, and serving.

        ``store`` / ``page_size`` / ``store_bytes`` select and size the
        posting-list backend (see ``index/store.py``).
        """
        cfg = KMeansConfig(k=k, max_iters=max_iters, init=init, tol=tol,
                           step_impl=step_impl, interpret=interpret,
                           planner=planner)
        key = jax.random.PRNGKey(seed)
        if chunk_size is None:
            xj = jnp.asarray(x)
            if pctx is None:
                centroids = KMeans(cfg).fit(key, xj).centroids
                blk = cfg.blocks_for(xj.shape[0], xj.shape[1],
                                     xj.dtype.itemsize)
                a, m = ops.flash_assign(xj, centroids.astype(xj.dtype),
                                        block_n=blk.assign_block_n,
                                        block_k=blk.assign_block_k,
                                        interpret=interpret)
            else:
                centroids, a, m = _train_sharded(pctx, cfg, key, xj)
            cap = capacity if capacity is not None else int(
                jnp.max(jnp.bincount(a, length=k)))
            index = cls(centroids, cap, max_cap=max_cap,
                        interpret=interpret, planner=planner, pctx=pctx,
                        store=store, page_size=page_size,
                        store_bytes=store_bytes, codec=codec,
                        rescore_mult=rescore_mult,
                        rescore_bytes=rescore_bytes, rescore=rescore,
                        router=router)
            if pctx is None:
                index._fold(xj, a, m)
            else:
                # per-shard statistics through the add program: a Pallas
                # kernel on the TPU cannot take mesh-sharded operands
                # outside a shard_map
                index._add_sharded(xj)
        else:
            # out-of-core: ChunkedKMeans trains (init from the first
            # chunk), then the same chunk stream is inverted via add().
            driver = ChunkedKMeans(cfg, chunk_size=chunk_size)
            first = next(driver._chunks(x))
            c0 = init_centroids(key, jnp.asarray(first), k, init)
            centroids, _ = driver.fit(x, c0)
            index = cls(centroids, capacity if capacity is not None else 8,
                        max_cap=max_cap, interpret=interpret,
                        planner=planner, pctx=pctx, store=store,
                        page_size=page_size, store_bytes=store_bytes,
                        codec=codec, rescore_mult=rescore_mult,
                        rescore_bytes=rescore_bytes, rescore=rescore,
                        router=router)
            for chunk in driver._chunks(x):
                index.add(chunk)
        # build-time evidence is the committed baseline, not drift:
        # start refresh() semantics from a clean pending slate
        index.stats = index.stats.merge(index._pending)
        index._pending = SufficientStats.zero(k, index.d)
        index._place()
        return index

    # ------------------------------------------------------------------
    # online mutation
    # ------------------------------------------------------------------

    def add(self, x_new) -> Array:
        """Assign, append, and account new vectors. Returns their cells.

        One FlashAssign pass gives the coarse cells; the batch is then
        CSR-ordered (stable argsort + segment offsets) so the bucket
        write is a disjoint vectorized scatter — and the batch sufficient
        statistics are folded into the pending ``SufficientStats`` so the
        next ``refresh`` can re-center without touching the points again.

        Under a ``pctx`` the batch is sharded over the data axes, the
        cells are found by the two-stage argmin, and the pending
        statistics arrive pre-reduced through the same O(K·d) psum tree
        as every other driver — already partitioned over the cells axis.
        """
        x_new = jnp.asarray(x_new, self.dtype)
        nan_evs: tuple = ()
        if self.faults is not None:   # injection seam (reliability.faults)
            evs = self.faults.poll("add")
            for ev in evs:
                if ev.kind == "drop_add":   # lost message: batch vanishes
                    return jnp.zeros((0,), jnp.int32)
                if ev.kind == "add_error":
                    raise InjectedFault(f"injected add failure ({ev})")
                if ev.kind == "latency":
                    time.sleep(ev.arg)
            nan_evs = tuple(e for e in evs if e.kind == "nan_stats")
        if x_new.shape[0] == 0:
            return jnp.zeros((0,), jnp.int32)
        if self.pctx is not None:
            a = self._add_sharded(x_new)
        else:
            # planned per observed batch-shape bucket (not a magic batch
            # size): a stream of same-bucket adds never replans
            blk = self._batch_blocks(x_new.shape[0])
            a, m = ops.flash_assign(x_new,
                                    self.centroids.astype(x_new.dtype),
                                    block_n=blk.assign_block_n,
                                    block_k=blk.assign_block_k,
                                    interpret=self.interpret)
            self._fold(x_new, a, m)
        for ev in nan_evs:   # corrupt *after* the fold: refresh must repair
            self._pending, _ = corrupt_stats(self._pending, int(ev.arg))
            self._place()
        return a

    def _add_sharded(self, x_new: Array) -> Array:
        """Sharded add: two-stage assign + per-shard owned statistics,
        one psum over the data axes — then the host-side CSR append."""
        pctx = self.pctx
        x_pad, mask, n = pctx.pad_points(x_new)
        prog = self._add_programs.get(x_pad.shape[0])
        if prog is None:
            prog = self._make_add_program()
            self._add_programs[x_pad.shape[0]] = prog
        a, s, cnt, j = prog(pctx.shard_points(x_pad),
                            pctx.put(mask, P(pctx.data_axes)),
                            self.centroids)
        a = a[:n]
        self._pending = self._pending.merge(SufficientStats(s, cnt, j))
        self._append(x_new, a)
        self._place()
        return a

    def _make_add_program(self):
        """One jitted shard_map'd assign+stats pass per padded batch
        shape (cached): the KernelPlanner is consulted at the per-shard
        batch/centroid shapes the program actually launches."""
        pctx, cfg, k = self.pctx, self._shard_cfg(), self.k
        ka = pctx.k_axis

        def shard_fn(x, mask, c_local):
            a, m = pctx.two_stage_assign(x, c_local, cfg)
            s, cnt = pctx.owned_stats(x, a, k, cfg, mask=mask)
            j = jax.lax.psum(jnp.sum(jnp.where(mask, m, 0.0)),
                             pctx.data_axes)
            return a, s, cnt, j

        fn = pctx.spmd(
            shard_fn,
            in_specs=(pctx.data_spec, P(pctx.data_axes),
                      pctx.centroid_spec),
            out_specs=(P(pctx.data_axes),
                       P(ka, None) if ka else P(None, None),
                       P(ka) if ka else P(None), P()))
        return jax.jit(fn)

    def _batch_blocks(self, n: int):
        """Assign/update tiles for an ``n``-row batch (planner-cached)."""
        return self.planner.block_config(
            n, self.k, self.d, jnp.dtype(self.dtype).itemsize)

    def _fold(self, x: Array, a: Array, m: Array) -> None:
        """Append a pre-assigned batch and account its statistics."""
        blk = self._batch_blocks(x.shape[0])
        s, cnt = ops.centroid_stats(
            x, a, k=self.k, block_n=blk.update_block_n,
            block_k=blk.update_block_k, interpret=self.interpret)
        self._pending = self._pending.merge(
            SufficientStats(s, cnt, jnp.sum(m)))
        self._append(x, a)

    def refresh(self, decay: float = 1.0, *, guard: bool = False,
                repair_dead: bool = False) -> "IVFIndex":
        """Commit pending evidence and re-center the coarse centroids.

        The warm-start ``partial_fit`` contract with the assignment pass
        hoisted into ``add``: pending batch statistics were computed at
        assignment time, so the commit is one O(K·d) merge + M-step —
        no pass over any stored vector. ``decay < 1`` exponentially
        down-weights old evidence (drifting corpora).

        ``guard=True`` sanitizes both evidence terms before the merge
        (``SufficientStats.sanitize``): a cluster carrying non-finite
        stats reverts to no-evidence and keeps its previous centroid —
        corruption never reaches the M-step. ``repair_dead=True``
        additionally re-seeds cells that hold no vectors *and* no
        evidence by splitting the heaviest cell (a perturbed copy of its
        centroid plus half its weight), so future adds can repopulate
        them. Both are opt-in: the default commit stays bitwise
        identical to the historical behaviour.
        """
        if self.faults is not None:   # injection seam (reliability.faults)
            for ev in self.faults.poll("refresh"):
                if ev.kind == "nan_stats":
                    self._pending, _ = corrupt_stats(self._pending,
                                                     int(ev.arg))
                elif ev.kind == "latency":
                    time.sleep(ev.arg)
        pending, base = self._pending, self.stats.scale(decay)
        if guard:
            pending, bad_p = pending.sanitize()
            base, bad_b = base.sanitize()
            self.repaired_cells += int(jnp.sum(bad_p)) + int(jnp.sum(bad_b))
        self.stats = base.merge(pending)
        self._pending = SufficientStats.zero(self.k, self.d)
        self.centroids = self.stats.finalize(self.centroids)
        if repair_dead:
            self.reseeded_cells += self._repair_dead_cells()
        self._cnorms = None   # centroids moved: the ||c||^2 cache is stale
        if self.router.kind != "flat":
            # coarse-consistency contract: re-assign moved fine
            # centroids to their groups (and periodically re-train the
            # coarse level — router-owned cadence, see index/router.py)
            self.router.refresh(np.asarray(self.centroids))
        self._place()   # merge/finalize are elementwise over K: re-pin
        return self

    def _repair_dead_cells(self, eps: float = 1e-3) -> int:
        """Re-seed cells with no stored vectors and no evidence.

        Host-side (runs at refresh cadence, not per query): each dead
        cell takes a perturbed copy of the heaviest cell's centroid and
        half its evidence weight — the classic split-the-largest empty-
        cluster repair, applied to the *index* so probes stop wasting
        ``nprobe`` slots on cells that can never return a candidate.
        Stored buckets are untouched; only centroids/stats move.
        """
        cnt = np.asarray(self.stats.counts).copy()
        stored = np.asarray(self.counts)
        dead = np.where((cnt <= 0.0) & (stored == 0))[0]
        if dead.size == 0:
            return 0
        c = np.asarray(self.centroids).copy()
        sums = np.asarray(self.stats.sums).copy()
        n = 0
        for cell in dead:
            donor = int(np.argmax(cnt))
            if cnt[donor] <= 1.0:   # nothing heavy enough to split
                break
            c[cell] = c[donor] * (1.0 + eps) + eps
            cnt[donor] *= 0.5
            sums[donor] *= 0.5
            cnt[cell] = cnt[donor]
            sums[cell] = c[cell] * cnt[cell]
            n += 1
        if n:
            self.centroids = jnp.asarray(c)
            self.stats = SufficientStats(jnp.asarray(sums),
                                         jnp.asarray(cnt),
                                         self.stats.inertia)
        return n

    def _append(self, x: Array, a: Array) -> None:
        """Append a batch in CSR order (sort-inverse, no per-point logic).

        The store computes slots and handles growth / page allocation /
        spill / eviction; ids stay monotone (spilled rows consume ids
        too), so WAL replay reproduces identical ids either way.
        """
        n = x.shape[0]
        if n == 0:
            return
        order, _ = csr_from_assignments(a, self.k)
        a_sorted = np.asarray(jnp.take(a, order))
        ids_new = (self.n_total + np.asarray(order)).astype(np.int32)
        x_sorted = jnp.take(x, order, axis=0)
        self.store.append(a_sorted, x_sorted, ids_new)
        self.n_total += n

    # ------------------------------------------------------------------
    # queries
    # ------------------------------------------------------------------

    def _gather_width(self, topk: int, nprobe: int) -> int:
        """The store's occupied per-cell candidate width for a geometry
        (>= ceil(topk/nprobe) so the scan's top-k always fits)."""
        return self.store.gather_width(-(-int(topk) // max(1, int(nprobe))))

    def _centroid_norms(self) -> Array:
        """Cached per-cell ``||c||^2`` strip, fed to every FlashProbe as
        ``c_sq`` — computed once per centroid set instead of inside each
        probe launch. ``refresh`` (the only path that moves centroids)
        invalidates it; under K-sharding it is pinned over the cells
        axis alongside the centroids themselves."""
        if self._cnorms is None:
            c32 = self.centroids.astype(self.dtype).astype(jnp.float32)
            cn = jnp.sum(c32 * c32, axis=-1)
            if self._k_sharded:
                cn = self.pctx.put(cn, P(self.pctx.k_axis))
            self._cnorms = cn
        return self._cnorms

    def _shard_ok_all(self) -> Array:
        """The healthy-path ``shard_ok`` mask as one cached device
        constant — the degraded path (a FaultPlan killing a shard)
        still uploads its bespoke mask, but healthy serving allocates
        nothing per call."""
        if self._shard_ok_dev is None:
            self._shard_ok_dev = jnp.ones(self.pctx.n_k_shards, bool)
        return self._shard_ok_dev

    def _rescore_cache(self):
        """The store's ``DeviceRescoreCache``, or None (fp32 payloads,
        or the host-reservoir oracle path)."""
        return getattr(self.store, "cache", None)

    def search_geometry(self, topk: int = 10, nprobe: int = 8,
                        nprobe_c: int | None = None) -> tuple:
        """Cheap geometry fingerprint for serving layers: it changes
        exactly when cached search programs would re-key (the store's
        occupancy crossed a ``gather_width`` bucket, or — two-level
        router — a re-grouping crossed a ``gcap`` bucket / moved the
        effective coarse width, or the device rescore cache grew its
        table), so a scheduler can re-pin its plans only then."""
        nprobe = min(nprobe, self.k)
        width = self._gather_width(topk, nprobe)
        rfp = (self.router.fingerprint(nprobe, nprobe_c)
               if self.router.kind == "two_level" else ())
        cache = self._rescore_cache()
        cfp = cache.fingerprint() if cache is not None else ()
        if self._k_sharded:
            return (nprobe, topk, width, self.pctx.n_k_shards) + rfp + cfp
        return (nprobe, topk, width) + rfp + cfp

    def _rescore_r(self, topk: int, nprobe: int, width: int) -> int:
        """Phase-1 proposal depth for two-phase search: ``rescore_mult``
        times the final ``topk``, clamped to the probed candidate pool
        (so full-nprobe searches can never ask for more proposals than
        candidates exist). ``rescore_mult="auto"`` defers to the
        codec-aware recall-target chooser
        (``heuristics.choose_rescore_mult``) per geometry; with a device
        rescore cache the chooser also sees the expected cache hit-rate
        (capacity over live rows — misses rescore from decoded codes
        already in registers, so a colder cache makes deeper proposal
        lists cheaper)."""
        mult = self.rescore_mult
        if mult is None:   # "auto"
            cache = self._rescore_cache()
            hit_rate = None
            if cache is not None:
                hit_rate = min(1.0, cache.capacity / max(1, self.n_total))
            mult = _heur.choose_rescore_mult(topk, self.d, nprobe * width,
                                             hit_rate=hit_rate)
        return min(max(topk, mult * topk), nprobe * width)

    def plan_search(self, b: int, topk: int = 10, nprobe: int = 8,
                    nprobe_c: int | None = None) -> tuple[int, ...]:
        """Plan (and cache) the search-stage kernels for a geometry.

        Returns ``(bqn, bqk, g, bw)`` — probe tiles and the list-major
        scan's ``(G, B_W)`` for a ``(b, d)`` query batch at this index's
        current ``(k, width)``, where ``width`` is the store's occupied
        gather width (a power-of-two bucket — occupancy growth changes
        the scan's tile count and naturally re-keys); the paths that
        still gather candidates (routed, q8, sharded) take the grouped
        scan's ``(bsb, bsc)`` in its place. The plan is cached on the
        index per ``(b, nprobe, topk, width)``, so the per-call chooser
        recompute this method replaces can never return to the hot path.
        Serving layers with a fixed padded batch shape
        (``serve.engine.SearchEngine``) call this once at config time.

        Under the two-level router the flat probe gives way to a
        coarse-probe + fine-route-scan pair, so the head of the tuple
        becomes four tiles ``(bcn, bck, bfb, bfc)`` and the cache key
        grows the router fingerprint ``(K_c, nprobe_c_eff, gcap)`` — a
        re-grouping that crosses a gcap bucket re-keys, exactly like
        store occupancy.

        Under a k-sharded ``pctx`` all stages are planned at the
        *per-shard* shapes each chip actually launches — K/P_k owned
        centroids and the owned candidate block — so plans stay correct
        under partitioning (a plan taken at the global shapes would
        size tiles for a kernel that never runs).
        """
        nprobe = min(nprobe, self.k)
        width = self._gather_width(topk, nprobe)
        routed = self.router.kind == "two_level"
        rfp = self.router.fingerprint(nprobe, nprobe_c) if routed else ()
        cache = self._rescore_cache()
        cfp = cache.fingerprint() if cache is not None else ()
        if self._k_sharded:
            kl = self.pctx.k_local(self.k)
            ll = min(nprobe, kl)          # max owned cells one query probes
            li = min(topk, ll * width)    # local result-list length
            pd = self.pctx.n_data_shards  # queries are data-sharded too
            bl = max(1, ((int(b) + pd - 1) // pd))
            geom = (int(b), nprobe, int(topk), width,
                    self.pctx.n_k_shards) + rfp + cfp
            probe_shape = (bl, kl, self.d, ll)
            scan_shape = (bl, ll * width, self.d, li)
            bq = bl
        else:
            geom = (int(b), nprobe, int(topk), width) + rfp + cfp
            probe_shape = (b, self.k, self.d, nprobe)
            scan_shape = (b, nprobe * width, self.d, topk)
            bq = int(b)
        plans = self._search_plans.get(geom)
        if plans is None:
            dt = self.dtype
            if routed:
                # routed head: coarse-probe tiles at (b, K_c) and
                # fine-route scan tiles over each query's npc·gcap
                # candidate centroids (the planner's "route" op models
                # the same split for byte accounting)
                kc, npc, gcap = rfp
                leff = min(nprobe, npc * gcap)
                rp = self.planner.plan("probe", (bq, kc, self.d, npc), dt)
                rs = self.planner.plan(
                    "scan", (bq, npc * gcap, self.d, leff), dt)
                head = (*rp.blocks, *rs.blocks)
            else:
                head = self.planner.plan("probe", probe_shape, dt).blocks
            if self.store.codec_kind != "fp32":
                # two-phase geometry: the quantized proposal scan is
                # planned as "scan_q8" (codec-aware bytes model) at the
                # proposal depth, the exact rescore as a plain f32 scan
                # over the R proposed rows (full batch — the rescore is
                # never sharded; proposals already crossed the wire)
                r = self._rescore_r(topk, nprobe, width)
                if self._k_sharded:
                    rl = min(r, scan_shape[1])
                    q8_shape = (scan_shape[0], scan_shape[1], self.d, rl)
                else:
                    q8_shape = (b, nprobe * width, self.d, r)
                q8 = self.planner.plan("scan_q8", q8_shape, jnp.int8)
                # with a device cache the rescore stage is planned as
                # its own "rescore" op (cache-gather-aware bytes model);
                # the host-oracle path keeps the plain f32 scan pricing
                rop = "scan" if cache is None else "rescore"
                rescore = self.planner.plan(
                    rop, (int(b), r, self.d, min(topk, r)), jnp.float32)
                plans = (*head, *q8.blocks, *rescore.blocks)
            elif self._list_major():
                scan = self.planner.plan(
                    "list_scan", (b * nprobe, self.k, width, self.d, topk),
                    dt)
                plans = (*head, *scan.blocks)
            else:
                scan = self.planner.plan("scan", scan_shape, dt)
                plans = (*head, *scan.blocks)
            self._search_plans[geom] = plans
        return plans

    def search(self, q, topk: int = 10, nprobe: int = 8, *,
               nprobe_c: int | None = None) -> tuple[Array, Array]:
        """Batched top-k search. q: (B, d) -> (ids (B, topk) int32,
        sq_dists f32 (B, topk)), ascending; ids of unfilled slots are -1.

        ``nprobe = k`` probes every cell: the result is exactly the
        brute-force top-k over all indexed vectors — under the
        two-level router too (the effective coarse width scales to full
        group coverage; see ``TwoLevelRouter.effective_nprobe_c``).
        ``nprobe_c`` overrides that coarse width explicitly (two-level
        router only; ignored by the flat router).
        """
        q = jnp.asarray(q, self.dtype)
        nprobe = min(nprobe, self.k)
        cand = nprobe * self.cap
        if topk > cand:
            raise ValueError(
                f"topk={topk} exceeds the probed candidate pool "
                f"nprobe*cap={cand}; raise nprobe or capacity")
        shard_ok = None
        if self.faults is not None:   # injection seam (reliability.faults)
            for ev in self.faults.poll("search"):
                if ev.kind == "latency":
                    time.sleep(ev.arg)
                elif ev.kind == "search_error":
                    raise InjectedFault(f"injected search failure ({ev})")
                elif ev.kind == "dead_shard":
                    if self._k_sharded:
                        nk = self.pctx.n_k_shards
                        shard_ok = np.ones(nk, bool)
                        shard_ok[int(ev.arg) % nk] = False
                    else:   # one replica == the whole index: hard fail
                        raise InjectedFault(
                            f"injected replica death ({ev})")
        if obs.enabled():
            obs.count("ivf.units")
            if self._list_major():
                obs.count("ivf.list_scan_units")
            obs.count("ivf.gathered_rows", self._gathered_rows(
                q.shape[0], topk, nprobe))
        if self.store.codec_kind != "fp32":
            return self._search_q8(q, topk, nprobe, shard_ok=shard_ok,
                                   nprobe_c=nprobe_c)
        if self._k_sharded:
            return self._search_sharded(q, topk, nprobe,
                                        shard_ok=shard_ok,
                                        nprobe_c=nprobe_c)
        st = self.store
        width = self._gather_width(topk, nprobe)
        if self.router.kind == "two_level":
            rt = self.router
            kc, npc, gcap = rt.fingerprint(nprobe, nprobe_c)
            leff = min(nprobe, npc * gcap)
            bcn, bck, bfb, bfc, bsb, bsc = self.plan_search(
                q.shape[0], topk, nprobe, nprobe_c)
            return _ivf_search_routed(
                q, self.centroids, rt.coarse, rt.coarse_sq, rt.members,
                st.device_arrays(), kind=st.kind, k=self.k, topk=topk,
                nprobe=nprobe, npc=npc, leff=leff, width=width,
                ps=st.page_param, nsh=st.n_shards, bcn=bcn, bck=bck,
                bfb=bfb, bfc=bfc, bsb=bsb, bsc=bsc,
                interpret=self.interpret)
        bqn, bqk, g, bw = self.plan_search(q.shape[0], topk, nprobe)
        return _ivf_search(q, self.centroids, self._centroid_norms(),
                           st.counts, st.device_arrays(),
                           kind=st.kind, topk=topk, nprobe=nprobe,
                           width=width,
                           ps=st.page_param, nsh=st.n_shards,
                           bqn=bqn, bqk=bqk, g=g, bw=bw,
                           interpret=self.interpret)

    def _list_major(self) -> bool:
        """Whether search takes the list-major scan (flat router, fp32
        payload, one device); the other paths gather candidates."""
        return (self.store.codec_kind == "fp32" and not self._k_sharded
                and self.router.kind != "two_level")

    def _gathered_rows(self, b: int, topk: int, nprobe: int) -> int:
        """Candidate rows one search call of ``b`` queries gathers, over
        all devices: ``nprobe`` lists of the gather width per query, or,
        on a cells-sharded mesh, the ``min(nprobe, K_local)`` owned lists
        each K-shard gathers for every query of the data-padded batch.
        The list-major scan gathers no candidates; its count is a bound on
        the store rows it reads, every tile of the width for every
        segment: it reads only each list's rows, and nothing for padding
        segments."""
        width = self._gather_width(topk, nprobe)
        if self._list_major():
            _, _, g, bw = self.plan_search(b, topk, nprobe)
            bw = self.store.page_param or min(bw, self.store.capacity)
            p = b * nprobe
            segs = -(-p // g) + min(self.k, p)
            return segs * -(-width // bw) * bw
        if not self._k_sharded:
            return b * nprobe * width
        pctx = self.pctx
        pd = pctx.n_data_shards
        b_pad = -(-b // pd) * pd
        ll = min(nprobe, pctx.k_local(self.k))
        return b_pad * ll * width * pctx.n_k_shards

    def _search_q8(self, q: Array, topk: int, nprobe: int,
                   shard_ok=None, nprobe_c: int | None = None
                   ) -> tuple[Array, Array]:
        """Two-phase search on a quantized store.

        Phase 1 proposes the top-``R`` candidates from the int8 payload
        (``R = rescore_mult * topk``, clamped to the probed pool) — on a
        mesh, each shard scans its owned buckets and the proposals merge
        exactly like the fp32 path's final top-k, followed by one
        O(b·R·d) psum row exchange so every proposal's row is
        batch-local. Phase 2 rescores the R rows at full precision for
        the final top-k, reading original rows from the rescore tier —
        the :class:`DeviceRescoreCache` gather fused into the same
        jitted program (zero host transfers; decoded codes where the
        cache missed), or, on the ``rescore="host"`` oracle path, the
        reservoir's host lookup by id. At full ``nprobe`` with R
        covering the live candidates this reproduces brute force
        exactly.
        """
        st = self.store
        b = q.shape[0]
        width = self._gather_width(topk, nprobe)
        r = self._rescore_r(topk, nprobe, width)
        cache = self._rescore_cache()
        if self._k_sharded:
            pctx = self.pctx
            pd = pctx.n_data_shards
            b_pad = ((b + pd - 1) // pd) * pd
            if b_pad != b:
                q = jnp.pad(q, ((0, b_pad - b), (0, 0)))
            routed = self.router.kind == "two_level"
            rfp = (self.router.fingerprint(nprobe, nprobe_c)
                   if routed else ())
            cfp = cache.fingerprint() if cache is not None else ()
            *_, brb, brc = self.plan_search(b_pad, topk, nprobe, nprobe_c)
            key = ("q8", b_pad, nprobe, topk, width) + rfp + cfp
            prog = self._sharded_search.get(key)
            if prog is None:
                prog = self._make_sharded_q8_candidates(b_pad, topk,
                                                        nprobe, nprobe_c)
                self._sharded_search[key] = prog
            ok = self._shard_ok_all() if shard_ok is None \
                else jnp.asarray(shard_ok)
            args = [pctx.shard_points(q), self.centroids,
                    self._centroid_norms()]
            if routed:
                rt = self.router
                args += [rt.coarse, rt.coarse_sq, rt.members]
            if cache is not None:
                # cache hits were substituted into the exchanged rows
                # inside the program; finish through the SAME
                # _ivf_rescore executable the host oracle uses (with a
                # vacuous overlay) so the scan's rounding matches the
                # oracle's bit for bit — still zero host operands
                ids, rows = prog(*args, *st.device_arrays(), ok,
                                 *st.cache_arrays())
                out_ids, dist = _ivf_rescore(
                    q, rows, ids, rows, jnp.zeros(ids.shape, bool),
                    topk=topk, bsb=brb, bsc=brc,
                    interpret=self.interpret)
                return out_ids[:b], dist[:b]
            ids, deq = prog(*args, *st.device_arrays(), ok)
        elif self.router.kind == "two_level":
            rt = self.router
            kc, npc, gcap = rt.fingerprint(nprobe, nprobe_c)
            leff = min(nprobe, npc * gcap)
            bcn, bck, bfb, bfc, bsb, bsw, brb, brc = self.plan_search(
                b, topk, nprobe, nprobe_c)
            if cache is not None:
                ids, deq, rows, found = _ivf_search_q8_routed_device(
                    q, self.centroids, rt.coarse, rt.coarse_sq,
                    rt.members, st.device_arrays(), *st.cache_arrays(),
                    kind=st.kind, k=self.k, r=r, nprobe=nprobe, npc=npc,
                    leff=leff, width=width, ps=st.page_param,
                    nsh=st.n_shards, bcn=bcn, bck=bck, bfb=bfb, bfc=bfc,
                    bsb=bsb, bsw=bsw, interpret=self.interpret)
                out_ids, dist = _ivf_rescore(q, deq, ids, rows, found,
                                             topk=topk, bsb=brb, bsc=brc,
                                             interpret=self.interpret)
                return out_ids[:b], dist[:b]
            ids, deq = _ivf_search_q8_routed(
                q, self.centroids, rt.coarse, rt.coarse_sq, rt.members,
                st.device_arrays(), kind=st.kind, k=self.k, r=r,
                nprobe=nprobe, npc=npc, leff=leff, width=width,
                ps=st.page_param, nsh=st.n_shards, bcn=bcn, bck=bck,
                bfb=bfb, bfc=bfc, bsb=bsb, bsw=bsw,
                interpret=self.interpret)
        else:
            bqn, bqk, bsb, bsw, brb, brc = self.plan_search(b, topk,
                                                            nprobe)
            if cache is not None:
                ids, deq, rows, found = _ivf_search_q8_device(
                    q, self.centroids, self._centroid_norms(),
                    st.device_arrays(), *st.cache_arrays(), kind=st.kind,
                    r=r, nprobe=nprobe, width=width, ps=st.page_param,
                    nsh=st.n_shards, bqn=bqn, bqk=bqk, bsb=bsb, bsw=bsw,
                    interpret=self.interpret)
                out_ids, dist = _ivf_rescore(q, deq, ids, rows, found,
                                             topk=topk, bsb=brb, bsc=brc,
                                             interpret=self.interpret)
                return out_ids[:b], dist[:b]
            ids, deq = _ivf_search_q8(
                q, self.centroids, self._centroid_norms(),
                st.device_arrays(), kind=st.kind,
                r=r, nprobe=nprobe, width=width, ps=st.page_param,
                nsh=st.n_shards, bqn=bqn, bqk=bqk, bsb=bsb, bsw=bsw,
                interpret=self.interpret)
        ids_np = np.asarray(ids)
        res = getattr(st, "reservoir", None)
        if res is not None:
            rows, found = res.lookup(ids_np)
        else:
            rows = np.zeros(ids_np.shape + (self.d,), np.float32)
            found = np.zeros(ids_np.shape, bool)
        out_ids, dist = _ivf_rescore(q, deq, ids, jnp.asarray(rows),
                                     jnp.asarray(found), topk=topk,
                                     bsb=brb, bsc=brc,
                                     interpret=self.interpret)
        return out_ids[:b], dist[:b]

    def _make_sharded_q8_candidates(self, b_pad: int, topk: int,
                                    nprobe: int,
                                    nprobe_c: int | None = None):
        """Phase-1 proposal program under cells sharding: the fp32
        sharded search's probe/compact/scan skeleton with the quantized
        kernel in the scan seat, a top-R (not top-k) merge, and one
        psum row exchange — each proposal's row is summed across shards
        through a one-hot id match (every live id is owned by exactly
        one shard), so the rescore sees the same (ids, rows) contract
        as the single-device phase 1. With a device rescore cache the
        cache partitions over the same cells axis and each shard
        substitutes its hits into the local rows *before* the exchange
        — the existing O(b·R·d) wire carries original fp32 rows instead
        of host re-uploads, and the whole program stays host-free.
        Under the two-level router the probe seat runs the
        replicated-coarse routed selection (``_route_cells_sharded``)
        instead."""
        pctx = self.pctx
        ka = pctx.k_axis
        k_local = pctx.k_local(self.k)
        st = self.store
        kind, ps = st.kind, st.page_param
        width = self._gather_width(topk, nprobe)
        r = self._rescore_r(topk, nprobe, width)
        ll = min(nprobe, k_local)       # a query probes <= ll owned cells
        rl = min(r, ll * width)         # local proposal-list length
        routed = self.router.kind == "two_level"
        if routed:
            _, npc, gcap = self.router.fingerprint(nprobe, nprobe_c)
            leff = min(nprobe, npc * gcap)
            bcn, bck, bfb, bfc, bsb, bsw, _, _ = self.plan_search(
                b_pad, topk, nprobe, nprobe_c)
        else:
            bqn, bqk, bsb, bsw, _, _ = self.plan_search(b_pad, topk,
                                                        nprobe)
        interpret = self.interpret
        k, d = self.k, self.d
        cached = self._rescore_cache() is not None

        def shard_fn(q, c_local, csq_local, *rest):
            if routed:
                coarse, coarse_sq, members, *rest = rest
            if cached:
                *rest, ckeys, crows = rest
            *arrays, anchors_l, shard_ok = rest
            bl = q.shape[0]
            alive = shard_ok[jax.lax.axis_index(ka)]
            lo = jax.lax.axis_index(ka) * k_local
            with jax.named_scope("ivf.probe"):
                if routed:
                    gcell = _route_cells_sharded(
                        pctx, ka, q, c_local, coarse, coarse_sq, members,
                        k=k, k_local=k_local, nprobe=nprobe, npc=npc,
                        leff=leff, bcn=bcn, bck=bck, bfb=bfb, bfc=bfc,
                        alive=alive, interpret=interpret)
                else:
                    idx, val = ops.flash_probe(q, c_local.astype(q.dtype),
                                               l=ll, block_n=bqn,
                                               block_k=bqk,
                                               interpret=interpret,
                                               want_dists=False,
                                               c_sq=csq_local)
                    gcell, _ = pctx.merge_topl(idx + lo, val, nprobe,
                                               valid=alive)   # (bl, nprobe)
            with jax.named_scope("ivf.gather"):
                rel = gcell - lo
                owned = jnp.logical_and(rel >= 0, rel < k_local)
                pos = jax.lax.broadcasted_iota(jnp.int32, (bl, nprobe), 1)
                order = jnp.argsort(jnp.where(owned, pos, nprobe),
                                    axis=1)[:, :ll]
                cell = jnp.take_along_axis(rel, order, axis=1)
                ok = jnp.take_along_axis(owned, order, axis=1)
                cell = jnp.where(ok, cell, k_local)
                codes, scales, cand_ids = _store.gather_cells_q8(
                    kind, tuple(arrays), cell, width, ps)
            with jax.named_scope("ivf.scan"):
                # residual-frame queries: the padding cell k_local maps to a
                # zero anchor row — its slots carry scale 0.0 and mask out
                anch = jnp.take(
                    jnp.concatenate([anchors_l.astype(jnp.float32),
                                     jnp.zeros((1, d), jnp.float32)], axis=0),
                    cell, axis=0)                        # (bl, ll, d)
                qp = q.astype(jnp.float32)[:, None, :] - anch
                lidx, lval = ops.flash_probe_grouped_q8(
                    qp, codes.reshape(bl, ll, width, d),
                    scales.reshape(bl, ll, width), l=rl,
                    block_b=bsb, block_w=bsw, interpret=interpret)
                ids_loc = jnp.where(
                    jnp.isfinite(lval),
                    jnp.take_along_axis(cand_ids, lidx, axis=1), -1)
                # same global probe-rank-major tie key as the fp32 merge
                gpos = (jnp.take_along_axis(order, lidx // width, axis=1)
                        * width + lidx % width)
                gids, _ = pctx.merge_topl(ids_loc, lval, r, tie=gpos,
                                          valid=alive)   # (bl, r)
                # row exchange: dequantize the local proposals, match them
                # against the merged id list, and psum — O(b·r·d) wire bytes
                deq_loc = (
                    jnp.take_along_axis(anch, (lidx // width)[:, :, None],
                                        axis=1)
                    + jnp.take_along_axis(codes, lidx[:, :, None], axis=1
                                          ).astype(jnp.float32)
                    * jnp.take_along_axis(scales, lidx, axis=1)[:, :, None])
                if cached:
                    # the local cache slice holds exactly the ids this
                    # shard owns (home cell fixed at append time): swap
                    # original fp32 rows in for the dequantized local
                    # proposals, then let the existing exchange carry them
                    crs, cfound = _rcache.cache_lookup(ckeys, crows, ids_loc)
                    deq_loc = jnp.where(cfound[:, :, None], crs, deq_loc)
                match = jnp.logical_and(
                    gids[:, :, None] == ids_loc[:, None, :],
                    (ids_loc >= 0)[:, None, :]).astype(jnp.float32)
                rows = jax.lax.psum(jnp.einsum("brl,bld->brd", match, deq_loc),
                                    ka)
                hit = jax.lax.psum(jnp.sum(match, axis=-1), ka)
                rows = jnp.where((hit > 0.0)[:, :, None], rows, _PAD_COORD)
            return gids, rows

        router_specs = ((P(None, None), P(None), P(None, None))
                        if routed else ())
        cache_specs = self._rescore_cache().shard_specs(ka) if cached \
            else ()
        fn = pctx.spmd(
            shard_fn,
            in_specs=(pctx.data_spec, P(ka, None), P(ka), *router_specs,
                      *st.shard_specs(ka), P(None), *cache_specs),
            out_specs=(P(pctx.data_axes, None),
                       P(pctx.data_axes, None, None)))
        return jax.jit(fn)

    def _search_sharded(self, q: Array, topk: int, nprobe: int,
                        shard_ok=None, nprobe_c: int | None = None
                        ) -> tuple[Array, Array]:
        """Two-stage sharded search (one shard_map'd program, cached per
        geometry). Queries are sharded over the data axes (each data
        shard searches its slice — no replicated compute; a ragged batch
        is padded and sliced back); per-batch cross-shard traffic is two
        (value, index) top-L merges over the cells axis —
        ``pctx.search_collective_bytes`` models it; the posting-list
        payloads never leave their owning shard.

        ``shard_ok`` ((P_k,) bool, default all-alive) is a traced input:
        a ``False`` entry blanks that K-shard's contribution to both
        merges (``merge_topl(valid=...)``) — the dead-shard degradation
        path shares the healthy program, no recompile."""
        pctx = self.pctx
        b = q.shape[0]
        pd = pctx.n_data_shards
        b_pad = ((b + pd - 1) // pd) * pd
        if b_pad != b:
            q = jnp.pad(q, ((0, b_pad - b), (0, 0)))
        routed = self.router.kind == "two_level"
        rfp = (self.router.fingerprint(nprobe, nprobe_c)
               if routed else ())
        key = (b_pad, nprobe, topk, self._gather_width(topk, nprobe)) + rfp
        prog = self._sharded_search.get(key)
        if prog is None:
            prog = self._make_sharded_search(b_pad, topk, nprobe, nprobe_c)
            self._sharded_search[key] = prog
        ok = self._shard_ok_all() if shard_ok is None \
            else jnp.asarray(shard_ok)
        args = [pctx.shard_points(q), self.centroids,
                self._centroid_norms()]
        if routed:
            rt = self.router
            args += [rt.coarse, rt.coarse_sq, rt.members]
        ids, dists = prog(*args, *self.store.device_arrays(), ok)
        return ids[:b], dists[:b]

    def _make_sharded_search(self, b_pad: int, topk: int, nprobe: int,
                             nprobe_c: int | None = None):
        pctx = self.pctx
        ka = pctx.k_axis
        k_local = pctx.k_local(self.k)
        st = self.store
        kind, ps = st.kind, st.page_param
        width = self._gather_width(topk, nprobe)
        ll = min(nprobe, k_local)       # a query probes <= ll owned cells
        li = min(topk, ll * width)      # local result-list length
        routed = self.router.kind == "two_level"
        if routed:
            _, npc, gcap = self.router.fingerprint(nprobe, nprobe_c)
            leff = min(nprobe, npc * gcap)
            bcn, bck, bfb, bfc, bsb, bsc = self.plan_search(
                b_pad, topk, nprobe, nprobe_c)
        else:
            bqn, bqk, bsb, bsc = self.plan_search(b_pad, topk, nprobe)
        interpret = self.interpret
        k = self.k

        def shard_fn(q, c_local, csq_local, *rest):
            if routed:
                coarse, coarse_sq, members, *rest = rest
            *arrays, shard_ok = rest
            bl = q.shape[0]             # per-data-shard query slice
            # a dead shard (reliability seam) contributes to neither merge
            alive = shard_ok[jax.lax.axis_index(ka)]
            lo = jax.lax.axis_index(ka) * k_local
            with jax.named_scope("ivf.probe"):
                if routed:
                    # stage 1': replicated coarse probe + owned-candidate
                    # fine scoring + the same cross-shard top-L merge
                    gcell = _route_cells_sharded(
                        pctx, ka, q, c_local, coarse, coarse_sq, members,
                        k=k, k_local=k_local, nprobe=nprobe, npc=npc,
                        leff=leff, bcn=bcn, bck=bck, bfb=bfb, bfc=bfc,
                        alive=alive, interpret=interpret)
                else:
                    # stage 1: local top-ll probe over the owned centroids,
                    # then the cross-shard top-nprobe merge — O(b·ll) wire
                    idx, val = ops.flash_probe(q, c_local.astype(q.dtype),
                                               l=ll, block_n=bqn,
                                               block_k=bqk,
                                               interpret=interpret,
                                               want_dists=False,
                                               c_sq=csq_local)
                    gcell, _ = pctx.merge_topl(idx + lo, val, nprobe,
                                               valid=alive)   # (bl, nprobe)
            with jax.named_scope("ivf.gather"):
                # stage 2: compact this shard's owned probed cells (stable:
                # global probe order preserved) into a fixed (bl, ll) block;
                # non-owned slots point at the padding cell k_local, which
                # the store's gather maps onto padding slots
                rel = gcell - lo
                owned = jnp.logical_and(rel >= 0, rel < k_local)
                pos = jax.lax.broadcasted_iota(jnp.int32, (bl, nprobe), 1)
                order = jnp.argsort(jnp.where(owned, pos, nprobe),
                                    axis=1)[:, :ll]
                cell = jnp.take_along_axis(rel, order, axis=1)
                ok = jnp.take_along_axis(owned, order, axis=1)
                cell = jnp.where(ok, cell, k_local)
                cand_x, cand_ids = _store.gather_cells(kind, tuple(arrays),
                                                       cell, width, ps)
            with jax.named_scope("ivf.scan"):
                # stage 3: local grouped scan of the owned buckets (payloads
                # stay on-shard), then the global top-k merge — O(b·topk).
                # The tie key is each candidate's *global probe-rank-major*
                # position — exactly the candidate-axis position the
                # single-device scan sees it at — so equal distances break
                # identically to `jax.lax.top_k` over the reference
                # candidate block, not toward the lower shard rank.
                lidx, lval = ops.flash_probe_grouped(
                    q, cand_x, l=li, block_b=bsb, block_c=bsc,
                    interpret=interpret, want_dists=False)
                ids_loc = jnp.take_along_axis(cand_ids, lidx, axis=1)
                gpos = (jnp.take_along_axis(order, lidx // width, axis=1)
                        * width + lidx % width)
                gids, gval = pctx.merge_topl(ids_loc, lval, topk, tie=gpos,
                                             valid=alive)
                q32 = q.astype(jnp.float32)
                gval = gval + jnp.sum(q32 * q32, axis=-1, keepdims=True)
                # blanked (dead-shard) slots carry inf: report them as honest
                # empty results, never a non-finite distance
                gval = jnp.where(jnp.isfinite(gval), jnp.maximum(gval, 0.0),
                                 0.0)
            return gids, gval

        router_specs = ((P(None, None), P(None), P(None, None))
                        if routed else ())
        fn = pctx.spmd(
            shard_fn,
            in_specs=(pctx.data_spec, P(ka, None), P(ka), *router_specs,
                      *st.shard_specs(ka), P(None)),
            out_specs=(P(pctx.data_axes, None), P(pctx.data_axes, None)))
        return jax.jit(fn)

    def search_brute(self, q, topk: int = 10) -> tuple[Array, Array]:
        """Dense brute-force reference over every indexed vector (the
        exactness/recall oracle — materializes the full score matrix)."""
        q = jnp.asarray(q, self.dtype)
        flat_x, flat_ids = self.store.flat()
        idx, dists = ref.probe_ref(q, flat_x, topk)
        return jnp.take(flat_ids, idx), dists

    # ------------------------------------------------------------------
    # durability (reliability.snapshot)
    # ------------------------------------------------------------------

    def save(self, directory: str, *, seqno: int = 0,
             extra: dict | None = None) -> str:
        """Atomic, mesh-agnostic snapshot of the full index state
        (store payload, counts, committed + pending stats, plan cache)
        — see ``reliability.snapshot.save_index``. ``seqno`` marks the
        WAL position this snapshot covers."""
        from repro.reliability.snapshot import save_index
        return save_index(self, directory, seqno=seqno, extra=extra)

    @classmethod
    def load(cls, directory: str, *, seqno: int | None = None, pctx=None,
             planner: "_plan.KernelPlanner | None" = None,
             interpret: bool | None = None) -> "IVFIndex":
        """Restore a snapshot onto any mesh (or none): arrays are stored
        unsharded in canonical form, placement is re-derived from
        ``pctx``."""
        from repro.reliability.snapshot import load_index
        return load_index(directory, seqno=seqno, pctx=pctx,
                          planner=planner, interpret=interpret)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def posting_lists(self) -> tuple[Array, Array]:
        """The CSR view ``(ids, offsets)``: list ``j`` is
        ``ids[offsets[j]:offsets[j+1]]`` (insertion order preserved)."""
        dense_ids = self.store.dense_ids()
        valid = (jax.lax.broadcasted_iota(jnp.int32, dense_ids.shape, 1)
                 < self.counts[:, None])
        ids = dense_ids[valid]               # row-major == cluster-major
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(self.counts)]).astype(jnp.int32)
        return ids, offsets

    def search_collective_bytes(self, b: int, topk: int = 10,
                                nprobe: int = 8) -> int:
        """Modeled per-batch cross-shard wire bytes of ``search`` (0 on a
        single device) — see ``ParallelContext.search_collective_bytes``
        and DESIGN.md "Parallel layer"."""
        if not self._k_sharded:
            return 0
        return self.pctx.search_collective_bytes(
            b, min(nprobe, self.k), topk, self.k, cap=self.cap, d=self.d)

    def __len__(self) -> int:
        return self.n_total

    def __repr__(self) -> str:
        shard = (f", cells_sharded x{self.pctx.n_k_shards}"
                 if self._k_sharded else "")
        codec = (f", codec={self.store.codec_kind}"
                 if self.store.codec_kind != "fp32" else "")
        rout = (f", router={self.router.kind}"
                if self.router.kind != "flat" else "")
        return (f"IVFIndex(k={self.k}, d={self.d}, n={self.n_total}, "
                f"cap={self.cap}, store={self.store.kind}{codec}{rout}"
                f"{shard})")
