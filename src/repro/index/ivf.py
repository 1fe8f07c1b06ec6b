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
  quantized and sharded searches still gather candidates and scan them
  with the grouped variant ``ops.flash_probe_grouped`` (or its q8 twin,
  followed by an exact **rescore**). The score matrix never exists in
  HBM at any stage;
- **online** — ``add`` assigns new vectors with FlashAssign, appends
  them to their lists in CSR batch order, and folds their sufficient
  statistics into the running per-cluster ``SufficientStats``
  (core.streaming); a periodic ``refresh`` commits the pending evidence
  and re-centers the coarse centroids via the warm-start
  ``finalize`` M-step — one O(K·d) reduction, never a refit.

Search is one composition of stages — route (flat or two-level) →
scan (list-major, or gather → grouped fp32 / q8 scan) → rescore (q8) —
fixed per query geometry by a ``_SearchSpec``: one jitted program,
``_ivf_search``, on one device, and one shard_map'd program per spec
(``_make_sharded_search``) on a cells-sharded mesh, over the same
stage functions.

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
two (value, index) list merges (q8 adds one O(b·R·d) row exchange). ``build`` trains through the same
context (data-parallel and/or two-stage K-sharded Lloyd), and
``add``/``refresh`` route the pending ``SufficientStats`` through the
same O(K·d) psum tree as every other driver.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple

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
    definition shared by the serve launcher, ``chip_smoke.py`` and the
    tests.
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


class _SearchSpec(NamedTuple):
    """Everything a search program closes over, built in one place
    (``IVFIndex._search_spec``): the one static argument of
    ``_ivf_search`` and the key of the sharded program cache. A named
    tuple, so building and hashing it per call costs the host little."""

    b: int                       # query rows of the unit (data-padded)
    topk: int
    nprobe: int
    width: int                   # the store's occupied gather width
    kind: str                    # store backend: "padded" | "paged"
    ps: int                      # page size (0: padded)
    nsh: int                     # store shards
    k: int
    shards: int = 0              # K-shards of a cells mesh (0: one device)
    route: tuple = ()            # two-level (K_c, nprobe_c, gcap); () flat
    codec: str = "fp32"          # "fp32" | "q8"
    r: int = 0                   # q8 proposal depth
    cache: tuple = ()            # device rescore cache fingerprint; () none
    interpret: bool | None = None
    route_tiles: tuple = ()      # (bqn, bqk) | (bcn, bck, bfb, bfc)
    scan_tiles: tuple = ()       # (g, bw) list-major | (bsb, bsc) | (bsb, bsw) q8
    rescore_tiles: tuple = ()    # (brb, brc), q8 only

    @property
    def routed(self) -> bool:
        return bool(self.route)

    @property
    def cached(self) -> bool:
        return bool(self.cache)

    @property
    def list_major(self) -> bool:
        """Flat router, fp32 payload, one device: the list-major scan."""
        return self.codec == "fp32" and not self.routed and not self.shards

    @property
    def leff(self) -> int:
        """Cells the fine route scan keeps (< nprobe on thin groups)."""
        _, npc, gcap = self.route
        return min(self.nprobe, npc * gcap)

    @property
    def k_local(self) -> int:
        return self.k // self.shards

    @property
    def ll(self) -> int:
        """Most owned cells one query probes on a K-shard."""
        return min(self.nprobe, self.k_local)

    @property
    def geometry(self) -> tuple:
        """What cached programs and plans re-key on (``search_geometry``)."""
        return ((self.nprobe, self.topk, self.width)
                + ((self.shards,) if self.shards else ())
                + self.route + self.cache)


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


def _probe(q: Array, c: Array, c_sq: Array, *, l: int, tiles: tuple,
           interpret: bool | None) -> tuple[Array, Array]:
    """FlashProbe: the ``l`` nearest of ``c`` per query (``c_sq`` its
    cached ``||c||^2`` strip), as ``(indices, ||q||^2-free scores)``."""
    bn, bk = tiles
    return ops.flash_probe(q, c.astype(q.dtype), l=l, block_n=bn,
                           block_k=bk, interpret=interpret,
                           want_dists=False, c_sq=c_sq)


def _coarse_cells(q: Array, coarse: Array, coarse_sq: Array,
                  members: Array, spec: _SearchSpec) -> Array:
    """The two-level router's coarse half: a FlashProbe picks each
    query's ``npc`` nearest group centroids and the member table expands
    them into its ``(B, npc·gcap)`` candidate fine cells (sentinel ``K``
    on thin-group padding)."""
    _, npc, gcap = spec.route
    cidx, _ = _probe(q, coarse, coarse_sq, l=npc,
                     tiles=spec.route_tiles[:2], interpret=spec.interpret)
    return jnp.take(members, cidx, axis=0).reshape(q.shape[0], npc * gcap)


def _with_pad_row(c: Array, dtype) -> Array:
    """``c`` plus one ``_PAD_COORD`` row: the row sentinel or not-owned
    cells gather, which can never win a probe."""
    return jnp.concatenate(
        [c.astype(dtype), jnp.full((1, c.shape[1]), _PAD_COORD, dtype)],
        axis=0)


def _route(q: Array, centroids: Array, c_sq: Array, route_arrays: tuple,
           spec: _SearchSpec) -> Array:
    """One device: the ``(B, nprobe)`` cells each query probes.

    Flat: FlashProbe over all K centroids. Two-level: a grouped
    FlashProbe over only the coarse candidates' ``npc·gcap`` fine
    centroids — the flat kernel's ``O(K·d)`` stream never happens — and
    cells come out in ``[0, K]`` (``K`` = no cell), ascending by distance;
    at ``leff < nprobe`` the tail is sentinel-padded."""
    if not spec.routed:
        probe, _ = _probe(q, centroids, c_sq, l=spec.nprobe,
                          tiles=spec.route_tiles, interpret=spec.interpret)
        return probe
    cand_cells = _coarse_cells(q, *route_arrays, spec)
    cand_c = jnp.take(_with_pad_row(centroids, q.dtype), cand_cells, axis=0)
    li, _ = ops.flash_probe_grouped(
        q, cand_c, l=spec.leff, block_b=spec.route_tiles[2],
        block_c=spec.route_tiles[3], interpret=spec.interpret,
        want_dists=False)
    cells = jnp.take_along_axis(cand_cells, li, axis=1)
    if spec.leff < spec.nprobe:
        cells = jnp.pad(cells, ((0, 0), (0, spec.nprobe - spec.leff)),
                        constant_values=spec.k)
    return cells


def _route_sharded(pctx, q: Array, c_local: Array, csq_local: Array,
                   route_arrays: tuple, *, lo: Array, alive: Array,
                   spec: _SearchSpec) -> Array:
    """One K-shard: the global ``(bl, nprobe)`` probe list, merged across
    shards (O(b·L) wire bytes; a dead shard contributes nothing).

    Flat: a local top-``ll`` probe over the owned centroids. Two-level:
    the coarse half runs on replicated state, so every shard derives the
    same candidate list with no wire traffic, and scores only the
    candidates it owns (the rest gather the padding row). The tie key is
    the candidate-axis position — the order the one-device route sees —
    so the merged list matches it entry for entry."""
    if not spec.routed:
        idx, val = _probe(q, c_local, csq_local, l=spec.ll,
                          tiles=spec.route_tiles, interpret=spec.interpret)
        gcell, _ = pctx.merge_topl(idx + lo, val, spec.nprobe, valid=alive)
        return gcell
    kl = spec.k_local
    cand_cells = _coarse_cells(q, *route_arrays, spec)
    relc = cand_cells - lo
    ownc = jnp.logical_and(relc >= 0, relc < kl)
    cand_c = jnp.take(_with_pad_row(c_local, q.dtype),
                      jnp.where(ownc, relc, kl), axis=0)
    fli, flv = ops.flash_probe_grouped(
        q, cand_c, l=spec.leff, block_b=spec.route_tiles[2],
        block_c=spec.route_tiles[3], interpret=spec.interpret,
        want_dists=False)
    fcell = jnp.take_along_axis(jnp.where(ownc, cand_cells, spec.k), fli,
                                axis=1)
    gcell, _ = pctx.merge_topl(fcell, flv, spec.nprobe, tie=fli,
                               valid=alive)
    return gcell


def _owned_cells(gcell: Array, lo: Array, spec: _SearchSpec
                 ) -> tuple[Array, Array]:
    """Compact a K-shard's owned probed cells (stable: global probe order
    kept) into a fixed ``(bl, ll)`` block of local indices; not-owned
    slots point at the padding cell ``k_local``, which the store's gather
    maps onto padding slots. Also returns each slot's global probe rank."""
    bl, nprobe = gcell.shape
    rel = gcell - lo
    owned = jnp.logical_and(rel >= 0, rel < spec.k_local)
    pos = jax.lax.broadcasted_iota(jnp.int32, (bl, nprobe), 1)
    order = jnp.argsort(jnp.where(owned, pos, nprobe), axis=1)[:, :spec.ll]
    cell = jnp.take_along_axis(rel, order, axis=1)
    ok = jnp.take_along_axis(owned, order, axis=1)
    return jnp.where(ok, cell, spec.k_local), order


def _gather(probe: Array, store_arrays: tuple, spec: _SearchSpec):
    """One device: the probed lists' candidate block — rows ``(x, ids)``,
    or on a q8 store ``(codes, scales, ids)``. Two-level routes pad with
    the sentinel cell ``K``: it gathers at a clamped index and masks back
    to padding (the ``_PAD_COORD`` row, or scale 0.0) and id -1. Returns
    ``(safe probe, sentinel mask or None, block)``."""
    k, width, fp32 = spec.k, spec.width, spec.codec == "fp32"
    pad, safe = None, probe
    if spec.routed:
        pad, safe = probe >= k, jnp.minimum(probe, k - 1)
    gather = _store.gather_global if fp32 else _store.gather_global_q8
    *payload, ids = gather(spec.kind, store_arrays if fp32
                           else store_arrays[:-1], safe, width, spec.ps,
                           spec.nsh)
    if pad is not None:
        padw = jnp.repeat(pad, width, axis=1)
        if fp32:
            payload[0] = jnp.where(padw[:, :, None], jnp.asarray(
                _PAD_COORD, payload[0].dtype), payload[0])
        else:
            payload[1] = jnp.where(padw, 0.0, payload[1])
        ids = jnp.where(padw, -1, ids)
    return safe, pad, (*payload, ids)


def _scan_q8(q: Array, anch: Array, codes: Array, scales: Array,
             cand_ids: Array, *, l: int, spec: _SearchSpec
             ) -> tuple[Array, Array, Array]:
    """The quantized proposal scan, in the residual frame: with
    ``q' = q - anchor[cell]`` the kernel's ``||q' - r||^2`` is the true
    quantized distance, comparable across probe slots. Returns the
    top-``l`` ``(slots, distances, ids)``; id -1 where fewer than ``l``
    live candidates exist."""
    b, n, d = anch.shape
    bsb, bsw = spec.scan_tiles
    qp = q.astype(jnp.float32)[:, None, :] - anch
    li, val = ops.flash_probe_grouped_q8(
        qp, codes.reshape(b, n, spec.width, d),
        scales.reshape(b, n, spec.width), l=l, block_b=bsb, block_w=bsw,
        interpret=spec.interpret)
    ids = jnp.where(jnp.isfinite(val),
                    jnp.take_along_axis(cand_ids, li, axis=1), -1)
    return li, val, ids


def _dequant(anch: Array, codes: Array, scales: Array, li: Array,
             width: int) -> Array:
    """The f32 rows of candidate slots ``li``: anchor + code · scale."""
    return (jnp.take_along_axis(anch, (li // width)[:, :, None], axis=1)
            + jnp.take_along_axis(codes, li[:, :, None], axis=1
                                  ).astype(jnp.float32)
            * jnp.take_along_axis(scales, li, axis=1)[:, :, None])


def _probe_rank_pos(order: Array, li: Array, width: int) -> Array:
    """A K-shard candidate's global probe-rank-major position — where the
    one-device scan sees it — the tie key of the cross-shard merges."""
    return jnp.take_along_axis(order, li // width, axis=1) * width \
        + li % width


@functools.partial(jax.jit, static_argnames=("spec",))
def _ivf_search(q: Array, centroids: Array, c_sq: Array | None,
                counts: Array | None, store_arrays: tuple,
                route_arrays: tuple = (), cache_arrays: tuple = (), *,
                spec: _SearchSpec):
    """The one-device search program, one jit per spec: route → scan.

    - fp32, flat router: the list-major scan (``_scan_lists``) →
      ``(ids, dists)``;
    - fp32, two-level router: gather → grouped scan → ``(ids, dists)``;
    - q8: gather codes → quantized top-``r`` proposal → ``(ids, deq)``,
      the dequantized rows the rescore falls back on; with a device
      rescore cache also its ``(rows, found)`` lookup, in the same
      program (no host transfer). The exact rescore is ``_ivf_rescore``.

    ``c_sq`` (the flat probe's ``||c||^2``), ``counts`` (the list-major
    scan's) and the router's and cache's arrays are passed only where
    the spec's stages read them."""
    with jax.named_scope("ivf.probe"):
        probe = _route(q, centroids, c_sq, route_arrays, spec)
    if spec.list_major:
        g, bw = spec.scan_tiles
        return _scan_lists(q, probe, counts, store_arrays, kind=spec.kind,
                           topk=spec.topk, width=spec.width, ps=spec.ps,
                           nsh=spec.nsh, g=g, bw=bw,
                           interpret=spec.interpret)
    with jax.named_scope("ivf.gather"):
        safe, pad, cand = _gather(probe, store_arrays, spec)
    with jax.named_scope("ivf.scan"):
        if spec.codec == "fp32":
            cand_x, cand_ids = cand
            bsb, bsc = spec.scan_tiles
            li, dist = ops.flash_probe_grouped(q, cand_x, l=spec.topk,
                                               block_b=bsb, block_c=bsc,
                                               interpret=spec.interpret)
            return jnp.take_along_axis(cand_ids, li, axis=1), dist
        codes, scales, cand_ids = cand
        anch = jnp.take(store_arrays[-1], safe, axis=0)   # (B, nprobe, d)
        if pad is not None:
            anch = jnp.where(pad[:, :, None], 0.0, anch)
        li, _, ids = _scan_q8(q, anch, codes, scales, cand_ids, l=spec.r,
                              spec=spec)
        deq = _dequant(anch, codes, scales, li, spec.width)
    if not spec.cached:
        return ids, deq
    with jax.named_scope("ivf.rescore"):
        rows, found = _rcache.cache_lookup(*cache_arrays, ids)
    return ids, deq, rows, found


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


# its own executable, shared by every tier and mesh: sharing the compiled
# program is what makes device-vs-host distances bitwise identical, not
# merely close (fusing it into the proposal program changes XLA's
# contraction order at the ulp level)
_ivf_rescore = functools.partial(
    jax.jit, static_argnames=("topk", "bsb", "bsc", "interpret")
)(_rescore_body)


def _make_sharded_search(pctx, spec: _SearchSpec, store_specs: tuple,
                         cache_specs: tuple):
    """The search program on a cells-sharded mesh: the one-device stages
    in one shard_map'd program per spec. Queries are sharded over the
    data axes; each K-shard routes (``_route_sharded``), compacts its
    owned probed cells (``_owned_cells``), gathers and scans them, and
    the lists merge across shards by the global probe-rank-major tie key.
    Posting-list payloads never leave their shard.

    - fp32: the local grouped scan, then the global top-k merge
      (O(b·topk) wire bytes) plus ``||q||^2`` → ``(ids, dists)``;
    - q8: the quantized top-R merge, then one O(b·R·d) psum row exchange
      (each proposal's row summed through a one-hot id match; every live
      id has one owner) → ``(ids, rows)``: the single-device phase-1
      contract. With a device rescore cache each shard swaps its cache
      hits into its local rows before the exchange.

    ``shard_ok`` ((P_k,) bool) is a traced input: a ``False`` entry
    blanks that K-shard in every merge — the dead-shard path shares the
    healthy program."""
    ka, width, ll = pctx.k_axis, spec.width, spec.ll
    nr, ns = 3 if spec.routed else 0, len(store_specs)

    def shard_fn(q, c_local, csq_local, *rest):
        route, arrays = rest[:nr], rest[nr:nr + ns]
        shard_ok, cache = rest[nr + ns], rest[nr + ns + 1:]
        rank = jax.lax.axis_index(ka)
        alive = shard_ok[rank]
        lo = rank * spec.k_local
        with jax.named_scope("ivf.probe"):
            gcell = _route_sharded(pctx, q, c_local, csq_local, route,
                                   lo=lo, alive=alive, spec=spec)
        with jax.named_scope("ivf.gather"):
            cell, order = _owned_cells(gcell, lo, spec)
            if spec.codec == "fp32":
                cand_x, cand_ids = _store.gather_cells(
                    spec.kind, arrays, cell, width, spec.ps)
            else:
                codes, scales, cand_ids = _store.gather_cells_q8(
                    spec.kind, arrays[:-1], cell, width, spec.ps)
        with jax.named_scope("ivf.scan"):
            if spec.codec == "fp32":
                bsb, bsc = spec.scan_tiles
                lidx, lval = ops.flash_probe_grouped(
                    q, cand_x, l=min(spec.topk, ll * width), block_b=bsb,
                    block_c=bsc, interpret=spec.interpret, want_dists=False)
                gids, gval = pctx.merge_topl(
                    jnp.take_along_axis(cand_ids, lidx, axis=1), lval,
                    spec.topk, tie=_probe_rank_pos(order, lidx, width),
                    valid=alive)
                q32 = q.astype(jnp.float32)
                gval = gval + jnp.sum(q32 * q32, axis=-1, keepdims=True)
                # blanked (dead-shard) slots carry inf: report them as
                # honest empty results, never a non-finite distance
                return gids, jnp.where(jnp.isfinite(gval),
                                       jnp.maximum(gval, 0.0), 0.0)
            # the padding cell k_local maps to a zero anchor row: its
            # slots carry scale 0.0 and mask out
            anch = jnp.take(jnp.concatenate(
                [arrays[-1].astype(jnp.float32),
                 jnp.zeros((1, q.shape[1]), jnp.float32)], axis=0),
                cell, axis=0)                            # (bl, ll, d)
            lidx, lval, ids_loc = _scan_q8(
                q, anch, codes, scales, cand_ids,
                l=min(spec.r, ll * width), spec=spec)
            gids, _ = pctx.merge_topl(
                ids_loc, lval, spec.r,
                tie=_probe_rank_pos(order, lidx, width), valid=alive)
            deq_loc = _dequant(anch, codes, scales, lidx, width)
            if spec.cached:
                crs, cfound = _rcache.cache_lookup(*cache, ids_loc)
                deq_loc = jnp.where(cfound[:, :, None], crs, deq_loc)
            match = jnp.logical_and(
                gids[:, :, None] == ids_loc[:, None, :],
                (ids_loc >= 0)[:, None, :]).astype(jnp.float32)
            rows = jax.lax.psum(
                jnp.einsum("brl,bld->brd", match, deq_loc), ka)
            hit = jax.lax.psum(jnp.sum(match, axis=-1), ka)
            return gids, jnp.where((hit > 0.0)[:, :, None], rows,
                                   _PAD_COORD)

    route_specs = (P(None, None), P(None), P(None, None)) if nr else ()
    dp = pctx.data_axes
    fn = pctx.spmd(
        shard_fn,
        in_specs=(pctx.data_spec, P(ka, None), P(ka), *route_specs,
                  *store_specs, P(None), *cache_specs),
        out_specs=(P(dp, None), P(dp, None) if spec.codec == "fp32"
                   else P(dp, None, None)))
    return jax.jit(fn)


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
        self._search_plans: dict[tuple, tuple[int, ...]] = {}
        self._sharded_search: dict[_SearchSpec, object] = {}
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

    def _search_spec(self, b: int, topk: int, nprobe: int,
                     nprobe_c: int | None = None, *,
                     plan: bool = True) -> _SearchSpec:
        """The one place a query geometry becomes a search program's
        static arguments. ``plan=False`` skips the tiles (the geometry
        alone, for ``search_geometry``); otherwise they come from the
        index's plan cache, keyed ``(b,) + spec.geometry``, planned on a
        miss (``_plan_tiles``)."""
        nprobe = min(nprobe, self.k)
        st, cache = self.store, self._rescore_cache()
        width = self._gather_width(topk, nprobe)
        q8 = st.codec_kind != "fp32"
        spec = _SearchSpec(
            b=int(b), topk=int(topk), nprobe=nprobe, width=width,
            kind=st.kind, ps=st.page_param, nsh=st.n_shards, k=self.k,
            shards=self.pctx.n_k_shards if self._k_sharded else 0,
            route=(self.router.fingerprint(nprobe, nprobe_c)
                   if self.router.kind == "two_level" else ()),
            codec=st.codec_kind,
            r=self._rescore_r(topk, nprobe, width) if q8 else 0,
            cache=cache.fingerprint() if cache is not None else (),
            interpret=self.interpret)
        if not plan:
            return spec
        key = (spec.b,) + spec.geometry
        tiles = self._search_plans.get(key)
        if tiles is None:
            tiles = self._search_plans[key] = self._plan_tiles(spec)
        head = 4 if spec.routed else 2
        return spec._replace(route_tiles=tiles[:head],
                             scan_tiles=tiles[head:head + 2],
                             rescore_tiles=tiles[head + 2:])

    def search_geometry(self, topk: int = 10, nprobe: int = 8,
                        nprobe_c: int | None = None) -> tuple:
        """Cheap geometry fingerprint for serving layers: it changes
        exactly when cached search programs would re-key (the store's
        occupancy crossed a ``gather_width`` bucket, or — two-level
        router — a re-grouping crossed a ``gcap`` bucket / moved the
        effective coarse width, or the device rescore cache grew its
        table), so a scheduler can re-pin its plans only then."""
        return self._search_spec(0, topk, nprobe, nprobe_c,
                                 plan=False).geometry

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
        scan's ``(bsb, bsc)`` in its place, and q8 appends the
        quantized scan's ``(bsb, bsw)`` and the rescore's ``(brb, brc)``
        instead. The plan is cached on the index per ``(b,) +``
        ``search_geometry``, so repeated traffic never reaches a chooser.
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
        s = self._search_spec(b, topk, nprobe, nprobe_c)
        return s.route_tiles + s.scan_tiles + s.rescore_tiles

    def _plan_tiles(self, spec: _SearchSpec) -> tuple[int, ...]:
        """Ask the planner for each stage's tiles at the shapes the
        spec's programs launch (see ``plan_search``)."""
        plan, d, dt = self.planner.plan, self.d, self.dtype
        nprobe, width, topk = spec.nprobe, spec.width, spec.topk
        if spec.shards:
            pd = self.pctx.n_data_shards  # queries are data-sharded too
            bq = max(1, -(-spec.b // pd))
            ll = spec.ll
            probe_shape = (bq, spec.k_local, d, ll)
            scan_shape = (bq, ll * width, d, min(topk, ll * width))
        else:
            bq = spec.b
            probe_shape = (bq, self.k, d, nprobe)
            scan_shape = (bq, nprobe * width, d, topk)
        if spec.routed:
            # coarse-probe tiles at (b, K_c) and fine-route scan tiles
            # over each query's npc·gcap candidate centroids
            kc, npc, gcap = spec.route
            head = (*plan("probe", (bq, kc, d, npc), dt).blocks,
                    *plan("scan", (bq, npc * gcap, d, spec.leff), dt).blocks)
        else:
            head = plan("probe", probe_shape, dt).blocks
        if spec.codec != "fp32":
            # the quantized proposal scan at the proposal depth
            # (codec-aware bytes model), the exact rescore over the R
            # proposed rows — full batch, never sharded; with a device
            # cache as its own "rescore" op (cache-gather-aware bytes)
            r = spec.r
            q8_shape = (scan_shape[:3] + (min(r, scan_shape[1]),)
                        if spec.shards else (bq, nprobe * width, d, r))
            q8 = plan("scan_q8", q8_shape, jnp.int8)
            rescore = plan("rescore" if spec.cached else "scan",
                           (spec.b, r, d, min(topk, r)), jnp.float32)
            return (*head, *q8.blocks, *rescore.blocks)
        if spec.list_major:
            scan = plan("list_scan", (bq * nprobe, self.k, width, d, topk),
                        dt)
        else:
            scan = plan("scan", scan_shape, dt)
        return (*head, *scan.blocks)

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

        One dispatch for every path: the spec (``_search_spec``) picks
        the program — ``_ivf_search`` on one device, the cached
        ``_make_sharded_search`` program on a cells-sharded mesh (the
        batch padded to a data-shard multiple and sliced back) — and a
        q8 store finishes through ``_rescore``.
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
        b = q.shape[0]
        if self._k_sharded:
            pd = self.pctx.n_data_shards
            b_pad = -(-b // pd) * pd
            if b_pad != b:
                q = jnp.pad(q, ((0, b_pad - b), (0, 0)))
        spec = self._search_spec(q.shape[0], topk, nprobe, nprobe_c)
        if obs.enabled():
            obs.count("ivf.units")
            if spec.list_major:
                obs.count("ivf.list_scan_units")
            obs.count("ivf.gathered_rows", self._gathered_rows(spec))
        st, rt = self.store, self.router
        route = (rt.coarse, rt.coarse_sq, rt.members) if spec.routed else ()
        cache = st.cache_arrays() if spec.cached else ()
        if spec.shards:
            prog = self._sharded_search.get(spec)
            if prog is None:
                ka = self.pctx.k_axis
                prog = self._sharded_search[spec] = _make_sharded_search(
                    self.pctx, spec, st.shard_specs(ka),
                    st.cache.shard_specs(ka) if spec.cached else ())
            ok = self._shard_ok_all() if shard_ok is None \
                else jnp.asarray(shard_ok)
            out = prog(self.pctx.shard_points(q), self.centroids,
                       self._centroid_norms(), *route, *st.device_arrays(),
                       ok, *cache)
        else:
            out = _ivf_search(
                q, self.centroids,
                None if spec.routed else self._centroid_norms(),
                st.counts if spec.list_major else None, st.device_arrays(),
                route, cache, spec=spec)
        if spec.codec != "fp32":
            out = self._rescore(q, out, spec)
        if q.shape[0] != b:
            out = (out[0][:b], out[1][:b])
        return out

    def _rescore(self, q: Array, out: tuple, spec: _SearchSpec
                 ) -> tuple[Array, Array]:
        """Phase 2 of q8 search: the exact rescore of the R proposals
        (``_ivf_rescore``). Original rows come from the device rescore
        cache — looked up inside the one-device program, or already
        swapped into the exchanged rows on a mesh (a vacuous overlay
        here) — else, on the ``rescore="host"`` oracle tier, from the
        reservoir's host lookup by id; the dequantized codes fill in
        where neither holds a row. At full ``nprobe`` with R covering
        the live candidates this reproduces brute force exactly."""
        ids, cand, *hit = out
        if hit:
            rows, found = hit
        elif spec.cached:
            rows, found = cand, jnp.zeros(ids.shape, bool)
        else:
            ids_np = np.asarray(ids)
            res = getattr(self.store, "reservoir", None)
            if res is not None:
                rows, found = res.lookup(ids_np)
            else:
                rows = np.zeros(ids_np.shape + (self.d,), np.float32)
                found = np.zeros(ids_np.shape, bool)
            rows, found = jnp.asarray(rows), jnp.asarray(found)
        brb, brc = spec.rescore_tiles
        return _ivf_rescore(q, cand, ids, rows, found, topk=spec.topk,
                            bsb=brb, bsc=brc, interpret=self.interpret)

    def _gathered_rows(self, spec: _SearchSpec) -> int:
        """Candidate rows one search unit gathers, over all devices:
        ``nprobe`` lists of the gather width per query, or, on a
        cells-sharded mesh, the ``ll`` owned lists each K-shard gathers
        for every query of the data-padded batch. The list-major scan
        gathers no candidates; its count is a bound on the store rows it
        reads, every tile of the width for every segment: it reads only
        each list's rows, and nothing for padding segments."""
        b, width = spec.b, spec.width
        if spec.list_major:
            g, bw = spec.scan_tiles
            bw = spec.ps or min(bw, self.store.capacity)
            p = b * spec.nprobe
            segs = -(-p // g) + min(self.k, p)
            return segs * -(-width // bw) * bw
        if spec.shards:
            return b * spec.ll * width * spec.shards
        return b * spec.nprobe * width

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
