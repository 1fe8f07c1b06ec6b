"""Batched serving engines.

``Engine`` — prefill + greedy/temperature decode, with an optional
flash-kmeans clustered-KV mode for long contexts. In clustered mode:
  1. runs dense prefill,
  2. clusters each layer's cached keys with flash-kmeans and rebuilds the
     cache in bucketed (sort-inverse) layout,
  3. decodes against the clustered cache; new tokens accumulate in a
     recent buffer, and when it fills the engine re-clusters
     *incrementally*: a warm-start ``partial_fit`` (core.streaming) over
     just the new keys — bucket statistics are carried forward as
     ``SufficientStats``, never refit from scratch — then the tokens are
     appended to their assigned buckets and the buffer resets.

``SearchEngine`` — batched vector search (query -> top-k ids) over a
FlashIVF index (repro.index), the online-retrieval analogue of the
clustered-KV flush schedule: inserts accumulate as pending
``SufficientStats`` and the coarse centroids are re-centered by a
periodic ``refresh`` instead of a refit.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.models import kmeans_attention as kma
from repro.models import model as M
from repro.models import transformer as T
from repro.models.common import Ctx
from repro.reliability.health import HealthCounters, HealthPolicy, \
    NonFiniteResult
from repro.reliability.validate import guard_batch
from repro.reliability.wal import AddLog

Array = jax.Array

_ENGINE_IDS = itertools.count(1)   # tells engines apart in the spans


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 2048
    mode: str = "dense"           # dense | clustered
    recent: int = 128
    kmeans_iters: int = 4
    temperature: float = 0.0      # 0 = greedy
    recluster_iters: int = 2      # partial_fit local iterations per flush
    recluster_decay: float = 1.0  # decay on bucket stats at each flush


def _is_clustered(x) -> bool:
    return isinstance(x, dict) and "centroids" in x


class Engine:
    def __init__(self, cfg: ArchConfig, params: Any, scfg: ServeConfig,
                 mesh=None, compute_dtype=jnp.float32):
        self.cfg = cfg
        self.scfg = scfg
        self.params = params
        self.ctx = Ctx(mesh=mesh, compute_dtype=compute_dtype)
        self.recluster_count = 0   # incremental flushes performed
        self._prefill = jax.jit(functools.partial(
            M.prefill, ctx=self.ctx, cfg=cfg, max_seq=scfg.max_seq))
        self._decode = jax.jit(functools.partial(
            M.decode_step, ctx=self.ctx, cfg=cfg))
        # per-layer incremental re-cluster (vmapped over the group axis of
        # each clustered sub-cache, jitted once per cache geometry)
        self._refresh = jax.jit(jax.vmap(functools.partial(
            kma.refresh_clustered_cache, iters=scfg.recluster_iters,
            decay=scfg.recluster_decay)))

    # ------------------------------------------------------------------

    def _cluster_caches(self, caches, seq_len: int):
        """Convert dense prefill caches to clustered layout."""
        cfg, scfg = self.cfg, self.scfg
        kc, cap = M.clustered_geometry(cfg, seq_len)
        kc = min(kc, max(4, seq_len // 8))
        hd = cfg.resolved_head_dim

        def convert(sub_cache):
            if not (isinstance(sub_cache, dict) and "k" in sub_cache):
                return sub_cache

            def one(k_, v_, pos):
                c = kma.build_clustered_cache(
                    k_[:, :seq_len], v_[:, :seq_len], kc=kc, capacity=cap,
                    iters=scfg.kmeans_iters)
                b = k_.shape[0]
                c.update(
                    recent_k=jnp.zeros((b, cfg.num_kv_heads, scfg.recent,
                                        hd), k_.dtype),
                    recent_v=jnp.zeros((b, cfg.num_kv_heads, scfg.recent,
                                        hd), k_.dtype),
                    rlen=jnp.zeros((), jnp.int32), pos=pos)
                return c

            return jax.vmap(one)(sub_cache["k"], sub_cache["v"],
                                 sub_cache["pos"])

        return jax.tree_util.tree_map(
            convert, caches,
            is_leaf=lambda x: isinstance(x, dict) and ("k" in x or "ssm" in x
                                                       or "mlstm" in x
                                                       or "slstm" in x
                                                       or "latent" in x))

    # ------------------------------------------------------------------

    def _recluster(self, caches):
        """Flush every clustered sub-cache through the warm-start
        ``partial_fit`` refresh — no full refit of the bucketed keys."""
        caches = jax.tree_util.tree_map(
            lambda x: self._refresh(x) if _is_clustered(x) else x,
            caches, is_leaf=_is_clustered)
        self.recluster_count += 1
        return caches

    def generate(self, tokens: Array, steps: int, *,
                 frontend: Array | None = None, key=None) -> Array:
        """tokens: (B, S) prompt -> (B, steps) generated ids."""
        logits, caches, cross = self._prefill(self.params, tokens,
                                              frontend=frontend)
        clustered = self.scfg.mode == "clustered"
        if clustered:
            caches = self._cluster_caches(caches, tokens.shape[1])
            # MLA keeps dense latents — no clustered leaves to refresh
            clustered = any(map(_is_clustered, jax.tree_util.tree_leaves(
                caches, is_leaf=_is_clustered)))
        out = []
        tok = self._sample(logits[:, -1], key, 0)
        # The flush schedule is deterministic host-side (rlen advances by
        # one per decode, resets to 0 on flush), so a host counter avoids
        # a per-token device sync that would serialize async dispatch.
        since_flush = 0
        for i in range(steps):
            out.append(tok)
            logits, caches = self._decode(self.params, tok, caches,
                                          cross_kv=cross)
            if clustered:
                since_flush += 1
                if since_flush >= self.scfg.recent:
                    caches = self._recluster(caches)
                    since_flush = 0
            tok = self._sample(logits[:, 0], key, i + 1)
        if not out:   # steps=0: prefill-only call, honest empty result
            return jnp.zeros((tokens.shape[0], 0), jnp.int32)
        return jnp.concatenate(out, axis=1)

    def _sample(self, logits: Array, key, i: int) -> Array:
        if self.scfg.temperature <= 0 or key is None:
            return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        k = jax.random.fold_in(key, i)
        return jax.random.categorical(
            k, logits / self.scfg.temperature)[:, None].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Vector-search serving (FlashIVF)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SearchConfig:
    topk: int = 10
    nprobe: int = 8
    nprobe_c: int | None = None   # coarse probe width (two-level router
                                  # only; None = the router's scaled
                                  # default — see index/router.py)
    query_batch: int = 256    # max formed batch (jit-cache shape ceiling)
    pipeline_depth: int = 2   # max un-synced search units in flight
                              # (double-buffered by default, like
                              # ChunkedKMeans; 1 = fully synchronous)
    refresh_every: int = 8    # add() batches between automatic refreshes
    refresh_decay: float = 1.0
    queue_max: int = 4096     # admission-queue bound (backpressure)
    # durability (reliability layer; None/0 = off)
    snapshot_dir: str | None = None   # index snapshots + WAL live here
    snapshot_every: int = 0           # adds between automatic snapshots
    wal_log_every: int = 1            # RPO knob (see reliability.wal)


class SearchEngine:
    """Continuous-batching query -> top-k serving over an ``IVFIndex``.

    The engine is a scheduler over an **admission queue**: ``submit``
    enqueues a search request (any number of rows), ``submit_add``
    enqueues an insert, and ``pump`` drains the queue in FIFO order —
    consecutive search requests are **coalesced** into one execution
    unit of up to ``query_batch`` rows (a request larger than the
    remaining unit budget is split; its tail keeps its place at the
    head of the line), and each unit is padded up to the next
    KernelPlanner-style power-of-two shape bucket, so ragged traffic of
    any size reuses a small fixed set of pinned jitted executables —
    never a fixed-shape rejection, never a per-request replan. Adds are
    applied between in-flight search units (the classic
    continuous-batching interleave), so heavy insert traffic never
    starves queries and vice versa. ``search``/``add`` remain as
    synchronous wrappers: submit + pump-to-completion.

    Inserts follow the same incremental contract as the clustered-KV
    cache — ``add`` assigns and appends, and every ``refresh_every``-th
    batch triggers a warm-start ``refresh`` (statistics merge + M-step,
    never a refit). The flush schedule is a host counter, mirroring
    ``Engine.generate``'s deterministic clustered-mode flushes.

    Plans are pinned per shape bucket at config time; the index exposes
    its search-geometry fingerprint (``search_geometry`` — the store's
    occupied gather width) and the scheduler re-pins only when that
    fingerprint moves (store occupancy crossed a width bucket), so
    steady-state traffic dispatches with zero chooser calls.

    Dispatch is **overlapped**: a search unit's result arrays are never
    ``block_until_ready``'d on the pump path — unit i+1 dispatches while
    unit i is still in flight, up to ``pipeline_depth`` un-synced units
    (double-buffered by default, the same discipline as
    ``ChunkedKMeans``). Completion happens at ``take`` (oldest-first)
    or on depth overflow, and ``latency_stats`` reports the honest
    dispatch-vs-complete split (p50/p99 over warm shape buckets) from
    the engine's spans.

    With tracing on (``repro.obs.enable()``) the engine records spans
    ``engine.submit`` and ``engine.take`` (``rid``), ``engine.pump``,
    and per unit ``engine.form`` (``unit``, ``rids``, ``rows``,
    ``bucket``), ``engine.dispatch``, ``engine.settle`` and
    ``engine.complete`` (``unit``); ``unit`` is the unit's number in
    ``batches_formed`` and ``engine`` tells engines apart.

    The engine is sharding-transparent: over an ``IVFIndex`` built with
    a ``ParallelContext`` (cells + posting lists partitioned over the
    mesh, ``launch.serve --mesh``), the same pinned plan / padded-batch
    contract holds — ``plan_search`` plans at the per-shard shapes and
    each unit is one shard_map'd program with O(b·L) cross-shard bytes
    (``index.search_collective_bytes`` models it).
    """

    def __init__(self, index, scfg: SearchConfig | None = None, *,
                 health: HealthPolicy | None = None, faults=None):
        self.index = index
        self.scfg = scfg or SearchConfig()
        self.health = health
        self.counters = HealthCounters()
        if faults is not None:   # attach the injector at the index seams
            index.faults = faults
        self.queries_served = 0
        self.adds_since_refresh = 0
        self.refresh_count = 0
        # durability: WAL + snapshots when a snapshot_dir is configured
        self.wal = AddLog(self.scfg.snapshot_dir,
                          log_every=self.scfg.wal_log_every) \
            if self.scfg.snapshot_dir else None
        self._seqno = 0            # last assigned insert-batch seqno
        self._adds_since_snap = 0
        self._replaying = False    # WAL replay re-enters add(): no re-log
        # admission-controlled pending-add queue (bounded requeue buffer
        # for inserts that failed transiently) + last-known-good clone
        self._pending_adds: collections.deque = collections.deque()
        self._lkg = None
        self._mark_healthy()
        # continuous batching: the admission queue, per-request result
        # slots, partial accumulators for split requests, and scheduler
        # counters
        self._queue: collections.deque = collections.deque()
        self._results: dict[int, tuple] = {}
        self._partials: dict[int, tuple[list, list]] = {}
        self._next_rid = 0
        self.batches_formed = 0       # search units executed
        self.coalesced_requests = 0   # requests that shared a unit
        self.interleaved_adds = 0     # adds applied between units
        # overlapped dispatch pipeline: a unit's arrays stay in flight
        # (never block_until_ready'd) until ``take`` needs them or the
        # pipeline depth overflows. ``overlap_hits`` counts dispatches
        # issued while an earlier unit was still un-synced — structural
        # overlap, the property the async hot path exists to create.
        self.overlap_hits = 0
        self._inflight: collections.deque = collections.deque()
        self._eid = next(_ENGINE_IDS)
        # unit shape buckets: powers of two up to query_batch (the same
        # snapping rule as KernelPlanner.bucket_dim, floored at 8)
        qb = self.scfg.query_batch
        buckets, bsz = [], 8
        while bsz < qb:
            buckets.append(bsz)
            bsz *= 2
        buckets.append(qb)
        self._buckets = buckets
        # Pin the kernel plans for every shape bucket this engine can
        # form at config time, so the first query (and every one after)
        # dispatches without touching a chooser. Store-occupancy growth
        # from heavy inserts moves the index's geometry fingerprint;
        # the scheduler re-pins exactly then (next search unit).
        self._pinned_geom = None
        self.pinned_plan = None
        if hasattr(index, "plan_search"):
            self._pin_plans()

    # ------------------------------------------------------------------
    # continuous batching: admission + batch formation + interleave
    # ------------------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    def _pin_plans(self) -> None:
        for bsz in self._buckets:
            plan = self.index.plan_search(bsz, self.scfg.topk,
                                          self.scfg.nprobe,
                                          self.scfg.nprobe_c)
        self.pinned_plan = plan
        if hasattr(self.index, "search_geometry"):
            self._pinned_geom = self.index.search_geometry(
                self.scfg.topk, self.scfg.nprobe, self.scfg.nprobe_c)

    def _admit(self, kind: str, payload) -> int:
        if len(self._queue) >= self.scfg.queue_max:
            raise RuntimeError(
                f"admission queue full ({self.scfg.queue_max} requests): "
                f"backpressure — pump() or raise queue_max")
        self._next_rid += 1
        self._queue.append((kind, self._next_rid, payload))
        return self._next_rid

    def submit(self, q: Array) -> int:
        """Enqueue a search request (any row count, including 0);
        returns a request id for ``take``. Sanitization happens at
        admission so the queue only holds servable rows."""
        with obs.span("engine.submit", rid=self._next_rid + 1):
            q = jnp.asarray(q)
            if self.health is not None:
                qh, rep = guard_batch(np.asarray(q), self.index.d,
                                      policy=self.health.query_policy,
                                      name="query batch")
                self.counters.queries_sanitized += rep.bad_rows
                q = jnp.asarray(qh, q.dtype)
            return self._admit("search", q)

    def submit_add(self, x) -> int:
        """Enqueue an insert; it is applied in FIFO position between
        search units (continuous-batching interleave). Returns a request
        id whose ``take`` yields the assigned cells."""
        return self._admit("add", x)

    def take(self, rid: int):
        """Block (pump) until request ``rid`` completes; return its
        result — ``(ids, dists)`` for a search, assigned cells for an
        add."""
        with obs.span("engine.take", rid=rid):
            while rid not in self._results:
                if not self.pump(1):
                    raise KeyError(f"unknown or lost request id {rid}")
            # sync discipline: the dispatch path never blocks on device
            # arrays — completion happens here, oldest-first, until no
            # in-flight unit still carries this request's rows
            while any(rid in rids for rids, _arrs, _u in self._inflight):
                self._complete_oldest()
            return self._results.pop(rid)

    def _complete_oldest(self) -> None:
        """Retire the oldest in-flight search unit: block until its
        arrays are ready."""
        if not self._inflight:
            return
        _rids, arrs, unit = self._inflight.popleft()
        with obs.span("engine.complete", unit=unit, engine=self._eid):
            jax.block_until_ready(arrs)

    def latency_stats(self) -> dict:
        """Dispatch-vs-complete latency split (ms), read from this
        engine's ``engine.dispatch`` and ``engine.complete`` spans:
        ``dispatch_*`` is the host's enqueue cost per unit, ``complete_*``
        is enqueue -> ``block_until_ready``. The first unit of each shape
        bucket pays compile and is left out. ``overlap_hits`` counts units
        dispatched while an earlier unit was still in flight.

        The percentiles need tracing on (``repro.obs.enable()``); with it
        off there are no spans and they read 0. They cover the units
        whose spans the record still holds: ``obs.reset()``, or the
        record's bound (``obs.MAX_SPANS``, newest kept), drops the older
        ones, and the first unit of each bucket after the drop is then
        left out as if it were that bucket's first."""
        mine = [(n, t0, t1, a) for n, t0, t1, _p, a in obs.snapshot()["spans"]
                if a.get("engine") == self._eid]
        bucket = {a["unit"]: a["bucket"] for n, _t0, _t1, a in mine
                  if n == "engine.form" and "bucket" in a}
        first = {}                      # bucket -> its first unit
        for unit in sorted(bucket):
            first.setdefault(bucket[unit], unit)
        warm = set(bucket) - set(first.values())
        start = {a["unit"]: t0 for n, t0, _t1, a in mine
                 if n == "engine.dispatch"}
        dispatch = [(t1 - t0) / 1e6 for n, t0, t1, a in mine
                    if n == "engine.dispatch" and a["unit"] in warm]
        complete = [(t1 - start[a["unit"]]) / 1e6 for n, _t0, t1, a in mine
                    if n == "engine.complete" and a["unit"] in warm
                    and a["unit"] in start]

        def pct(xs: list[float], p: float) -> float:
            return float(np.percentile(np.asarray(xs), p)) if xs else 0.0
        return {"dispatch_p50_ms": pct(dispatch, 50),
                "dispatch_p99_ms": pct(dispatch, 99),
                "complete_p50_ms": pct(complete, 50),
                "complete_p99_ms": pct(complete, 99),
                "overlap_hits": self.overlap_hits,
                "inflight": len(self._inflight)}

    def pump(self, max_units: int | None = None) -> int:
        """Drain the admission queue: each unit is either one coalesced
        padded search batch or one interleaved add. Returns the number
        of units executed (0 = queue empty)."""
        done = 0
        with obs.span("engine.pump"):
            while self._queue and (max_units is None or done < max_units):
                if self._queue[0][0] == "add":
                    _, rid, x = self._queue.popleft()
                    self._results[rid] = self.add(x)
                    self.interleaved_adds += 1
                else:
                    self._run_search_unit()
                done += 1
        return done

    def _run_search_unit(self) -> None:
        """Form and execute one search unit: coalesce consecutive queued
        search requests up to ``query_batch`` rows (splitting an
        oversized request — its tail stays at the head of the line),
        snap the unit to its power-of-two shape bucket, run it through
        the health ladder, and scatter results back per request."""
        unit_no = self.batches_formed + 1
        with obs.span("engine.form", unit=unit_no, engine=self._eid) as sp:
            formed = self._form_unit()
            if formed is None:
                return
            parts, rows, bucket, unit = formed
            if obs.enabled():
                sp.set(rids=tuple(p[0] for p in parts), rows=rows,
                       bucket=bucket)
        # overlapped dispatch: start this unit while earlier units'
        # arrays may still be in flight (jax arrays are async; adds
        # interleave safely because dispatch captured its operands)
        if self._inflight:
            self.overlap_hits += 1
        with obs.span("engine.dispatch", unit=unit_no, engine=self._eid):
            ids, dists = self._search_padded(unit)
        self.batches_formed += 1
        self.queries_served += rows
        with obs.span("engine.settle", unit=unit_no, engine=self._eid):
            lo = 0
            for rid, qpart, has_tail in parts:
                n = qpart.shape[0]
                self._settle(rid, ids[lo:lo + n], dists[lo:lo + n],
                             has_tail=has_tail)
                lo += n
        self._inflight.append(
            ({rid for rid, _q, _t in parts}, (ids, dists), unit_no))
        while len(self._inflight) > max(1, self.scfg.pipeline_depth):
            self._complete_oldest()

    def _form_unit(self):
        """Pop the next unit's requests off the queue: ``(parts, rows,
        bucket, unit)`` with ``parts`` the ``(rid, rows, has_tail)``
        slices and ``unit`` the padded ``(bucket, d)`` query block; None
        when the head of the queue held only empty requests."""
        qb = self.scfg.query_batch
        parts: list[tuple[int, Array, bool]] = []   # (rid, rows, has_tail)
        rows = 0
        while self._queue and self._queue[0][0] == "search" and rows < qb:
            kind, rid, q = self._queue.popleft()
            n = q.shape[0]
            if n == 0:   # zero-row request: immediate honest empty result
                self._settle(rid, jnp.zeros((0, self.scfg.topk), jnp.int32),
                             jnp.zeros((0, self.scfg.topk), jnp.float32),
                             has_tail=False)
                continue
            tk = min(n, qb - rows)
            if n > tk:   # split: the tail keeps its place in line
                self._queue.appendleft((kind, rid, q[tk:]))
            parts.append((rid, q[:tk], n > tk))
            rows += tk
            if n > tk:
                break
        if not parts:
            return None
        if len(parts) > 1:
            self.coalesced_requests += len(parts)
        unit = parts[0][1] if len(parts) == 1 else \
            jnp.concatenate([p[1] for p in parts], axis=0)
        bucket = next(bb for bb in self._buckets if bb >= rows)
        if rows < bucket:
            unit = jnp.pad(unit, ((0, bucket - rows), (0, 0)))
        # re-pin only when the index's geometry fingerprint moved (store
        # occupancy crossed a gather-width bucket, or — two-level
        # router — a refresh re-grouped across a gcap bucket)
        if self._pinned_geom is not None:
            geom = self.index.search_geometry(self.scfg.topk,
                                              self.scfg.nprobe,
                                              self.scfg.nprobe_c)
            if geom != self._pinned_geom:
                self._pin_plans()
        return parts, rows, bucket, unit

    def _settle(self, rid: int, ids: Array, dists: Array, *,
                has_tail: bool) -> None:
        """Accumulate one request's slice; finish it once no tail
        remains queued."""
        si, sd = self._partials.get(rid, ([], []))
        si.append(ids)
        sd.append(dists)
        if has_tail:
            self._partials[rid] = (si, sd)
            return
        self._partials.pop(rid, None)
        if len(si) == 1:
            self._results[rid] = (si[0], sd[0])
        else:
            self._results[rid] = (jnp.concatenate(si, axis=0),
                                  jnp.concatenate(sd, axis=0))

    # ------------------------------------------------------------------
    # queries
    # ------------------------------------------------------------------

    def search(self, q: Array) -> tuple[Array, Array]:
        """q: (B, d) -> (ids (B, topk), dists) for any B — the
        synchronous wrapper over the continuous-batching queue: admit,
        pump to completion, return. Batches larger than ``query_batch``
        run as multiple coalesced units; smaller ones snap to a pinned
        power-of-two bucket — arbitrary B, zero replans. With a
        ``HealthPolicy`` attached this never raises and never returns
        non-finite distances: queries are sanitized at admission and
        every unit walks the degradation ladder (see
        ``reliability.health``)."""
        return self.take(self.submit(q))

    def _search_padded(self, q: Array) -> tuple[Array, Array]:
        if self.health is None:
            return self.index.search(q, topk=self.scfg.topk,
                                     nprobe=self.scfg.nprobe,
                                     nprobe_c=self.scfg.nprobe_c)
        return self._ladder(q)

    def _attempt(self, q: Array, nprobe: int) -> tuple[Array, Array]:
        """One configured search; non-finite output counts as a failure."""
        ids, dists = self.index.search(q, topk=self.scfg.topk,
                                       nprobe=nprobe,
                                       nprobe_c=self.scfg.nprobe_c)
        if self.health.check_finite \
                and not bool(np.isfinite(np.asarray(dists)).all()):
            raise NonFiniteResult("search returned non-finite distances")
        return ids, dists

    def _ladder(self, q: Array) -> tuple[Array, Array]:
        """The degradation ladder (``reliability.health`` docstring):
        retry/backoff -> nprobe halving -> brute force -> last-known-good
        -> honest black-hole. Never raises."""
        pol, ctr = self.health, self.counters
        nprobe = min(self.scfg.nprobe, self.index.k)
        attempts = pol.max_retries + 1   # retries only at the full nprobe
        delay = pol.backoff_s
        while True:
            for i in range(attempts):
                try:
                    ids, dists = self._attempt(q, nprobe)
                    if nprobe >= min(self.scfg.nprobe, self.index.k):
                        ctr.searches_ok += 1
                    else:
                        ctr.nprobe_degraded += 1
                    return ids, dists
                except Exception:
                    if i < attempts - 1:
                        ctr.retries += 1
                        if delay > 0:
                            time.sleep(delay)
                            delay *= pol.backoff_factor
            if nprobe > pol.min_nprobe:   # rung 2: cheaper, lower recall
                nprobe = max(pol.min_nprobe, nprobe // 2)
                attempts = 1
                continue
            break
        if pol.brute_fallback:   # rung 3: no probe stage left to fail
            try:
                ids, dists = self.index.search_brute(q, topk=self.scfg.topk)
                if not bool(np.isfinite(np.asarray(dists)).all()):
                    raise NonFiniteResult("brute force non-finite")
                ctr.brute_fallbacks += 1
                return ids, dists
            except Exception:
                pass
        if pol.lkg_fallback and self._lkg is not None:   # rung 4: stale
            try:
                ids, dists = self._lkg.search(q, topk=self.scfg.topk,
                                              nprobe=nprobe)
                if not bool(np.isfinite(np.asarray(dists)).all()):
                    raise NonFiniteResult("lkg non-finite")
                ctr.lkg_fallbacks += 1
                return ids, dists
            except Exception:
                pass
        ctr.blackholed += 1   # rung 5: honest empty rows
        b = q.shape[0]
        return (jnp.full((b, self.scfg.topk), -1, jnp.int32),
                jnp.zeros((b, self.scfg.topk), jnp.float32))

    # ------------------------------------------------------------------
    # ingestion
    # ------------------------------------------------------------------

    def add(self, x_new: Array) -> Array:
        """Online insert; auto-refreshes on the host-side flush schedule.

        With durability configured the batch is WAL-logged *before* it
        touches the index (log-before-apply); with a ``HealthPolicy``
        it is validated first (``insert_policy``) and a failed apply is
        parked on the bounded admission queue and retried on the next
        call instead of being lost — or rejected outright once the
        queue is full (backpressure, not unbounded memory)."""
        x = np.asarray(x_new)
        if self.health is not None:
            x, rep = guard_batch(x, self.index.d,
                                 policy=self.health.insert_policy,
                                 name="insert batch")
            if rep.action == "dropped":
                self.counters.insert_rows_dropped += rep.bad_rows
        if x.shape[0] == 0:
            return jnp.zeros((0,), jnp.int32)
        self._seqno += 1
        if self.wal is not None and not self._replaying:
            self.wal.append(self._seqno, x)
        self._drain_pending()
        a = self._apply(self._seqno, x)
        if self.adds_since_refresh >= self.scfg.refresh_every:
            self.refresh()
        self._adds_since_snap += 1
        if (self.scfg.snapshot_every and not self._replaying
                and self._adds_since_snap >= self.scfg.snapshot_every):
            self.snapshot()
        return a

    def _apply(self, seqno: int, x) -> Array:
        """Apply one logged batch; requeue (bounded) on failure."""
        try:
            a = self.index.add(x)
        except Exception:
            if self.health is not None and len(self._pending_adds) \
                    < self.health.max_pending_adds:
                self._pending_adds.append((seqno, x))
                self.counters.adds_requeued += 1
            else:
                self.counters.adds_rejected += 1
            if self.health is None:
                raise
            return jnp.zeros((0,), jnp.int32)
        self.adds_since_refresh += 1
        return a

    def _drain_pending(self) -> None:
        """Retry parked inserts (admission queue) ahead of new work."""
        for _ in range(len(self._pending_adds)):
            seqno, x = self._pending_adds.popleft()
            self._apply(seqno, x)

    def refresh(self) -> None:
        """Commit pending evidence — guarded/self-repairing under a
        ``HealthPolicy`` (NaN stats rows zeroed, dead cells re-seeded),
        and a failed commit leaves the schedule armed for retry instead
        of propagating."""
        pol = self.health
        try:
            if pol is not None:
                r0 = self.index.repaired_cells
                d0 = self.index.reseeded_cells
                self.index.refresh(decay=self.scfg.refresh_decay,
                                   guard=pol.guard_refresh,
                                   repair_dead=pol.repair_dead)
                self.counters.stats_repaired += \
                    self.index.repaired_cells - r0
                self.counters.dead_cells_reseeded += \
                    self.index.reseeded_cells - d0
            else:
                self.index.refresh(decay=self.scfg.refresh_decay)
        except Exception:
            if pol is None:
                raise
            self.counters.refresh_failures += 1
            return
        self.adds_since_refresh = 0
        self.refresh_count += 1
        self._mark_healthy()

    def _mark_healthy(self) -> None:
        """Refresh the last-known-good clone (rung 4 of the ladder)."""
        if self.health is not None and self.health.lkg_fallback:
            from repro.reliability.snapshot import clone_index
            self._lkg = clone_index(self.index)

    # ------------------------------------------------------------------
    # durability
    # ------------------------------------------------------------------

    def snapshot(self) -> str:
        """Snapshot the index (+ the engine's schedule counters) as of
        the current WAL position, then truncate the covered WAL tail."""
        if not self.scfg.snapshot_dir:
            raise ValueError("snapshot() needs scfg.snapshot_dir")
        path = self.index.save(
            self.scfg.snapshot_dir, seqno=self._seqno,
            extra={"adds_since_refresh": self.adds_since_refresh,
                   "refresh_count": self.refresh_count,
                   "queries_served": self.queries_served})
        if self.wal is not None:
            self.wal.truncate(self._seqno)
        self._adds_since_snap = 0
        self.counters.snapshots_written += 1
        return path

    @classmethod
    def recover(cls, directory: str, scfg: SearchConfig | None = None, *,
                health: HealthPolicy | None = None, faults=None,
                pctx=None, planner=None,
                interpret: bool | None = None) -> "SearchEngine":
        """Crash recovery: load the latest snapshot (onto any mesh) and
        replay the WAL tail through the live ``add`` path — bitwise the
        index an uninterrupted run would hold (same batches, same order,
        same deterministic refresh schedule, restored from the
        manifest's ``extra``)."""
        from repro.index.ivf import IVFIndex
        from repro.reliability.snapshot import read_manifest
        index = IVFIndex.load(directory, pctx=pctx, planner=planner,
                              interpret=interpret)
        scfg = dataclasses.replace(scfg or SearchConfig(),
                                   snapshot_dir=directory)
        eng = cls(index, scfg, health=health, faults=faults)
        manifest = read_manifest(directory)
        extra = manifest.get("extra", {})
        eng.adds_since_refresh = extra.get("adds_since_refresh", 0)
        eng.refresh_count = extra.get("refresh_count", 0)
        eng.queries_served = extra.get("queries_served", 0)
        eng._seqno = int(manifest.get("seqno", 0))
        covered = eng._seqno
        eng._replaying = True
        try:
            for seqno, x in eng.wal.replay(after=covered):
                eng._seqno = seqno - 1   # add() reassigns exactly seqno
                eng.add(x)
                eng.counters.wal_records_replayed += 1
        finally:
            eng._replaying = False
        eng._mark_healthy()
        return eng
