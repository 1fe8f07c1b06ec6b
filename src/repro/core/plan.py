"""KernelPlanner — one cache-aware planning layer for every kernel dispatch.

The paper's deployability claim (§4.3) is that kernel configurations are
chosen *analytically* and *cached* — never re-derived on a hot path and
never exhaustively re-tuned per call. The closed-form math lives in
``core.heuristics``; this module owns everything around it:

- **the plan contract** — ``plan(op, shape, dtype) -> KernelPlan``: one
  call answers "what impl + block shapes do I run this op with on this
  hardware", with a VMEM footprint audit and the modeled HBM traffic
  attached so callers (and benchmarks) can reason about the decision;
- **the cache layers** — a process-level memo keyed on
  ``(op, padded-shape-bucket, dtype-itemsize, hardware)`` (batch-like
  dims are bucketed to the next power of two, so a stream of ragged
  batch sizes shares one plan), backed by a persistent on-disk JSON
  cache so repeated launches skip planning entirely;
- **hardware** — ``detect_hardware()`` maps ``jax.devices()`` onto the
  ``heuristics.HARDWARE_TABLE``; the CPU backend (interpret mode) plans
  against ``TPU_V5E``, and an unknown device is an error;
- **measured refinement** — ``refine="measure"`` (or ``fold_measured``)
  folds ``core.autotune.exhaustive_tune`` results back into the cache,
  making the exhaustive tuner a planner *backend* instead of an island:
  the measured blocks win for that shape bucket from then on, including
  across launches via the disk cache.

Every driver (``KMeans``, ``ChunkedKMeans``, ``StreamingKMeans``, the
distributed shard program, ``IVFIndex``/``SearchEngine``) and every
``kernels.ops`` wrapper resolves its blocks through this layer; the
``chooser_calls`` counter exists so tests can assert that repeated
same-geometry dispatch is a pure cache hit.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile

import jax
import jax.numpy as jnp

from repro.core import heuristics
from repro.kernels.ops import BlockConfig

# Bump whenever KernelPlan fields or chooser semantics change: a disk
# cache written by an older version is *stale*, and is ignored (not
# fatal) rather than deserialized into wrong plans.
CACHE_VERSION = 2

OPS = ("assign", "update", "step", "probe", "scan", "scan_q8", "route",
       "rescore", "list_scan")

_SHAPE_ARITY = {"assign": 3, "update": 3, "step": 3, "probe": 4, "scan": 4,
                "scan_q8": 4, "route": 4, "rescore": 4, "list_scan": 5}

# which shape positions are batch-like (bucketed to the next power of
# two); geometry dims (k, d, l) stay exact — they pin the VMEM footprint
_BUCKET_DIMS = {"assign": (0,), "update": (0,), "step": (0,),
                "probe": (0,), "scan": (0, 1), "scan_q8": (0, 1),
                "route": (0,), "rescore": (0, 1), "list_scan": (0,)}

_ITEMSIZE_DTYPE = {2: jnp.bfloat16, 4: jnp.float32, 8: jnp.float64}


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def bucket_dim(v: int) -> int:
    """Next power of two >= v (floor 8 = one sublane)."""
    return max(8, 1 << max(0, int(v) - 1).bit_length())


def _itemsize(dtype) -> int:
    if isinstance(dtype, int):
        return dtype
    return jnp.dtype(dtype).itemsize


def detect_hardware(devices=None) -> heuristics.Hardware:
    """Map ``jax.devices()`` onto the ``heuristics.HARDWARE_TABLE``.

    Matching is by substring of ``device_kind`` (lowercased, spaces
    stripped), most specific first. Only the CPU backend — where the
    kernels run in interpret mode and the block shapes only need to be
    *feasible* — plans against ``TPU_V5E``. Everything else fails
    loudly rather than planning for the wrong chip: a device enumeration
    that raises propagates, and an empty device list, a TPU generation
    missing from the table, or any other platform raises.
    """
    if devices is None:
        devices = jax.devices()
    if not devices:
        raise RuntimeError("detect_hardware: no devices to plan for")
    dev = devices[0]
    if dev.platform == "cpu":
        return heuristics.TPU_V5E
    kind = str(dev.device_kind).lower().replace(" ", "")
    if dev.platform == "tpu":
        for needle, hw in heuristics.HARDWARE_TABLE:
            if needle in kind:
                return hw
    raise RuntimeError(
        f"detect_hardware: no HARDWARE_TABLE row for platform "
        f"{dev.platform!r}, device_kind {dev.device_kind!r}")


def hardware_by_name(name: str | None) -> heuristics.Hardware:
    """Resolve a ``Hardware`` row from its ``name`` (as carried by a
    ``KernelPlan``); ``None``/unknown falls back to the default planner's
    detected hardware."""
    if name is not None:
        for _, hw in heuristics.HARDWARE_TABLE:
            if hw.name == name:
                return hw
    return default_planner().hw


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """The planner's answer for one (op, shape bucket, dtype, hardware).

    ``blocks`` are the op's own two tile dims — ``(B_N, B_K)`` for the
    shared-centroid kernels, ``(B_B, B_C)`` for the grouped scan,
    ``(G, B_W)`` for the list-major scan. ``block``
    is the full ``BlockConfig`` (all three kmeans legs) for the ops that
    have one (``assign``/``update``/``step``); ``None`` for probe/scan.
    ``vmem_bytes`` is the audited working-set footprint at ``blocks`` and
    ``hbm_bytes`` the modeled per-call traffic at the planning shape —
    carried on the plan so dispatch decisions stay inspectable.
    """
    op: str
    shape: tuple          # bucketed planning shape
    itemsize: int
    hw: str
    impl: str             # assign: "flash" | update: "sort_inverse"
                          # step: "fused"/"two_pass" | probe/scan: kernel name
    blocks: tuple         # the op's (minor-major) tile dims
    block: BlockConfig | None
    vmem_bytes: int
    vmem_budget: int
    hbm_bytes: float
    source: str           # "heuristic" | "measured"

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["shape"] = list(self.shape)
        d["blocks"] = list(self.blocks)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "KernelPlan":
        blk = d.get("block")
        return cls(
            op=str(d["op"]), shape=tuple(d["shape"]),
            itemsize=int(d["itemsize"]), hw=str(d["hw"]),
            impl=str(d["impl"]), blocks=tuple(int(v) for v in d["blocks"]),
            block=None if blk is None else BlockConfig(
                **{k: int(v) for k, v in blk.items()}),
            vmem_bytes=int(d["vmem_bytes"]),
            vmem_budget=int(d["vmem_budget"]),
            hbm_bytes=float(d["hbm_bytes"]), source=str(d["source"]))


def _default_cache_path() -> str | None:
    """On-disk plan cache location; ``REPRO_PLAN_CACHE`` overrides
    (a path, or ``off``/``0``/empty to disable persistence)."""
    env = os.environ.get("REPRO_PLAN_CACHE")
    if env is not None:
        if env.strip().lower() in ("", "off", "0", "none"):
            return None
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "flash_kmeans",
                        "plans.json")


class KernelPlanner:
    """Single entry point for kernel dispatch planning.

    >>> planner = KernelPlanner()                    # detects hardware
    >>> p = planner.plan("step", (1_000_000, 1024, 128))
    >>> p.impl, p.blocks, p.vmem_bytes               # inspectable decision
    >>> blk = planner.block_config(n, k, d, dtype_bytes)

    Cache layers, consulted in order: the in-process memo, the on-disk
    JSON cache (loaded lazily, ignored when corrupt or version-stale),
    and finally the closed-form choosers of ``core.heuristics`` (each
    such computation bumps ``chooser_calls`` — the counter hook the
    zero-replan regression tests assert on). ``refine="measure"``
    upgrades a heuristic plan with ``autotune.exhaustive_tune`` results.
    """

    def __init__(self, hw: heuristics.Hardware | None = None, *,
                 cache_path: str | os.PathLike | None = None,
                 persist: bool = True):
        self.hw = hw if hw is not None else detect_hardware()
        self.cache_path = (str(cache_path) if cache_path is not None
                           else (_default_cache_path() if persist else None))
        self._mem: dict[str, KernelPlan] = {}
        # raw disk payload (every valid-version entry, including other
        # hardware's plans) — preserved verbatim on save so one cache
        # file can serve a mixed fleet without cross-truncation
        self._disk_raw: dict[str, dict] = {}
        self._disk_loaded = False
        self.hits = 0
        self.misses = 0
        self.disk_entries_loaded = 0
        self.chooser_calls = 0   # closed-form planning passes actually run
        self.measure_calls = 0   # exhaustive-tune refinements actually run

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def plan(self, op: str, shape, dtype=jnp.float32, *,
             blk: BlockConfig | None = None, refine: str | None = None,
             interpret: bool | None = None) -> KernelPlan:
        """Plan one kernel dispatch.

        ``shape``: ``(n, k, d)`` for assign/update/step, ``(n, k, d, l)``
        for probe, ``(b, c, d, l)`` for scan, ``(b·nprobe, k, width, d,
        l)`` for list_scan. ``dtype`` may be a dtype or
        a raw itemsize. ``blk`` pins an explicit ``BlockConfig`` (the
        plan is then judged — and cached — for those tiles, e.g. the
        fused-feasibility check at user-forced blocks). ``refine`` in
        ``(None, "heuristic", "measure")``: ``"measure"`` runs (or reuses)
        an exhaustive tune for this shape bucket and folds the measured
        blocks into the cached plan.
        """
        if op not in OPS:
            raise ValueError(f"unknown plan op {op!r}; expected one of {OPS}")
        shape = tuple(int(s) for s in shape)
        if len(shape) != _SHAPE_ARITY[op]:
            raise ValueError(f"op {op!r} expects a shape of arity "
                             f"{_SHAPE_ARITY[op]}, got {shape}")
        if refine not in (None, "heuristic", "measure"):
            raise ValueError(f"unknown refine backend {refine!r}")
        b = _itemsize(dtype)
        bshape = self._bucket(op, shape)
        self._load_disk()
        if blk is not None:
            # if the pinned blocks are exactly what the base plan chose,
            # reuse it instead of forking a blk-keyed entry
            base = self._mem.get(self._key(op, bshape, b))
            if base is not None and base.block == blk:
                blk = None
        key = self._key(op, bshape, b, blk)
        got = self._mem.get(key)
        if got is not None:
            self.hits += 1
            if (refine == "measure" and got.source != "measured"
                    and op in ("assign", "update", "step")):
                return self._measure(op, bshape, b, interpret)
            return got
        self.misses += 1
        plan = self._compute(op, bshape, b, blk)
        self._store(plan, key)
        if refine == "measure" and op in ("assign", "update", "step"):
            return self._measure(op, bshape, b, interpret)
        return plan

    def block_config(self, n: int, k: int, d: int,
                     dtype_bytes: int = 4) -> BlockConfig:
        """Full ``BlockConfig`` (all three kmeans legs) for a geometry."""
        return self.plan("step", (n, k, d), dtype_bytes).block

    def step_impl(self, n: int, k: int, d: int, dtype_bytes: int = 4,
                  blk: BlockConfig | None = None) -> str:
        """``"fused"`` or ``"two_pass"`` — the crossover rule, judged at
        ``blk`` when given (the tiles that will actually launch)."""
        return self.plan("step", (n, k, d), dtype_bytes, blk=blk).impl

    def fold_measured(self, n: int, k: int, d: int, dtype=jnp.float32, *,
                      report=None, interpret: bool | None = None
                      ) -> KernelPlan:
        """Fold an exhaustive-tune result into the cache for this bucket.

        ``report``: a ``core.autotune.TuneReport``; when ``None`` the
        tuner is run here (the expensive path — one-time, then cached on
        disk). Updates the assign, update, *and* step entries of the
        shape bucket: the measured legs replace the heuristic's, the
        fused leg and the crossover decision are re-judged at the merged
        blocks. Returns the refined step plan.
        """
        b = _itemsize(dtype)
        bshape = self._bucket("step", (n, k, d))
        if report is None:
            from repro.core import autotune
            report = autotune.exhaustive_tune(
                *bshape, dtype=_ITEMSIZE_DTYPE.get(b, jnp.float32),
                hw=self.hw, interpret=interpret)
            self.measure_calls += 1
        base = self._compute("step", bshape, b, None)
        merged = dataclasses.replace(
            base.block,
            assign_block_n=report.best.assign_block_n,
            assign_block_k=report.best.assign_block_k,
            update_block_n=report.best.update_block_n,
            update_block_k=report.best.update_block_k)
        step = self._compute("step", bshape, b, merged, source="measured")
        self._store(step)
        return step

    def counters(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "chooser_calls": self.chooser_calls,
                "measure_calls": self.measure_calls,
                "disk_entries_loaded": self.disk_entries_loaded,
                "entries": len(self._mem)}

    def clear(self, disk: bool = False) -> None:
        self._mem.clear()
        self._disk_raw.clear()
        self._disk_loaded = False
        if disk and self.cache_path:
            try:
                os.remove(self.cache_path)
            except OSError:
                pass

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _bucket(self, op: str, shape: tuple) -> tuple:
        return tuple(bucket_dim(s) if i in _BUCKET_DIMS[op] else int(s)
                     for i, s in enumerate(shape))

    def _key(self, op: str, bshape: tuple, itemsize: int,
             blk: BlockConfig | None = None) -> str:
        blk_part = (None if blk is None else
                    [getattr(blk, f.name) for f in dataclasses.fields(blk)])
        return json.dumps([CACHE_VERSION, op, list(bshape), itemsize,
                           self.hw.name, blk_part])

    def _compute(self, op: str, s: tuple, b: int,
                 blk: BlockConfig | None, source: str = "heuristic"
                 ) -> KernelPlan:
        """Run the closed-form choosers for one cache miss."""
        H = heuristics
        hw = self.hw
        budget = H.vmem_budget(hw)
        self.chooser_calls += 1
        mk = lambda **kw: KernelPlan(op=op, shape=s, itemsize=b, hw=hw.name,
                                     vmem_budget=budget, source=source, **kw)
        if op in ("assign", "update", "step"):
            n, k, d = s
            cfg = blk if blk is not None else H.choose_blocks(
                n, k, d, dtype_bytes=b, hw=hw)
            if op == "assign":
                bn, bk = cfg.assign_block_n, cfg.assign_block_k
                return mk(impl="flash", blocks=(bn, bk), block=cfg,
                          vmem_bytes=H.assign_footprint(bn, bk, d, b),
                          hbm_bytes=H.assign_bytes_flash(n, k, d, b))
            if op == "update":
                bn, bk = cfg.update_block_n, cfg.update_block_k
                return mk(impl="sort_inverse", blocks=(bn, bk), block=cfg,
                          vmem_bytes=H.update_footprint(bn, bk, d, b),
                          hbm_bytes=H.update_bytes_sort_inverse(n, k, d, b))
            impl = H.choose_step_impl(n, k, d, dtype_bytes=b, hw=hw, blk=cfg)
            if impl == "fused":
                bn, bk = cfg.fused_block_n, cfg.fused_block_k
                k_pad = _round_up(k, bk)
                return mk(impl=impl, blocks=(bn, bk), block=cfg,
                          vmem_bytes=H.fused_footprint(bn, bk, d, b, k_pad),
                          hbm_bytes=H.lloyd_bytes_fused(n, k, d, b))
            vmem = max(
                H.assign_footprint(cfg.assign_block_n, cfg.assign_block_k,
                                   d, b),
                H.update_footprint(cfg.update_block_n, cfg.update_block_k,
                                   d, b))
            return mk(impl=impl,
                      blocks=(cfg.assign_block_n, cfg.assign_block_k),
                      block=cfg, vmem_bytes=vmem,
                      hbm_bytes=(H.assign_bytes_flash(n, k, d, b)
                                 + H.update_bytes_sort_inverse(n, k, d, b)))
        if op == "probe":
            n, k, d, l = s
            bn, bk = H.choose_probe_blocks(n, k, d, l, dtype_bytes=b, hw=hw)
            l_pad = _round_up(max(1, l), hw.sublane)
            return mk(impl="online_topl", blocks=(bn, bk), block=None,
                      vmem_bytes=H.probe_footprint(bn, bk, l_pad, d, b),
                      hbm_bytes=H.probe_bytes_flash(n, k, d, l, b))
        if op == "route":
            # shape (b, k, d, nprobe): choose the two-level routing
            # geometry for a K-cell index probed at depth nprobe.
            # ``blocks`` carries (K_c, nprobe_c) — routing *parameters*,
            # not tile dims; the coarse/fine stages take their own tile
            # plans as ordinary "probe"/"scan" ops at the routed shapes.
            n, k, d, l = s
            kc, npc = H.choose_route_params(k, l, hw=hw)
            gcap = H.route_group_cap(k, kc)
            flat = H.probe_bytes_flash(n, k, d, l, b)
            routed = H.probe_bytes_routed(n, k, kc, npc, gcap, d, l, b)
            impl = "two_level" if routed < flat else "flat"
            cbn, cbk = H.choose_probe_blocks(n, kc, d, npc,
                                             dtype_bytes=b, hw=hw)
            npc_pad = _round_up(max(1, npc), hw.sublane)
            return mk(impl=impl, blocks=(kc, npc), block=None,
                      vmem_bytes=H.probe_footprint(cbn, cbk, npc_pad, d, b),
                      hbm_bytes=min(routed, flat))
        if op == "scan_q8":
            bq, c, d, l = s
            bb, bw = H.choose_scan_q8_blocks(bq, c, d, l, hw=hw)
            l_pad = _round_up(max(1, l), hw.sublane)
            # codec-aware scan traffic: the shifted query block (f32,
            # one row per probe slot — amortized into the bq*d term),
            # int8 codes + one f32 scale per candidate row, the (B, L)
            # index/dist pair out
            hbm = (bq * d * 4.0 + bq * c * (d * 1 + 4)
                   + 2 * bq * l * 4)
            return mk(impl="grouped_scan_q8", blocks=(bb, bw), block=None,
                      vmem_bytes=H.scan_q8_footprint(bb, bw, l_pad, d),
                      hbm_bytes=hbm)
        if op == "list_scan":
            # shape (b·nprobe, k, width, d, topk): the list-major scan's
            # (G, B_W) tiles. Traffic: each probed list's rows once per
            # segment, at most every list once plus one re-stream per
            # extra segment, the (S, G, d) query groups in and the
            # (S, G, L) slot/score pair out
            p, k, w, d, l = s
            g, bw = H.choose_list_scan_blocks(p, k, w, d, l, dtype_bytes=b,
                                              hw=hw)
            l_pad = _round_up(max(1, l), hw.sublane)
            segs = -(-p // g) + min(k, p)
            hbm = (min(k, p) + -(-p // g)) * w * d * b \
                + segs * g * (d * b + 2 * l_pad * 4)
            return mk(impl="list_major_scan", blocks=(g, bw), block=None,
                      vmem_bytes=H.list_scan_footprint(g, bw, l_pad, d, b),
                      hbm_bytes=float(hbm))
        if op == "rescore":
            # phase-2 exact verify fed by the device rescore cache:
            # shape (b, R, d, topk). Tiles are the plain grouped scan's
            # (the kernel is identical); traffic adds the cache gather —
            # an int32 key lane + found mask per proposed row on top of
            # the f32 candidate block (hits read the cache pool, misses
            # reuse the decoded rows the proposer already materialized,
            # so the row bytes are the same f32 stream either way)
            bq, c, d, l = s
            bb, bc = H.choose_scan_blocks(bq, c, d, l, dtype_bytes=b, hw=hw)
            l_pad = _round_up(max(1, l), hw.sublane)
            hbm = (bq * d + bq * c * d) * b + bq * c * 8.0 \
                + 2 * bq * l * 4
            return mk(impl="grouped_rescore", blocks=(bb, bc), block=None,
                      vmem_bytes=H.scan_footprint(bb, bc, l_pad, d, b),
                      hbm_bytes=hbm)
        bq, c, d, l = s
        bb, bc = H.choose_scan_blocks(bq, c, d, l, dtype_bytes=b, hw=hw)
        l_pad = _round_up(max(1, l), hw.sublane)
        # grouped scan traffic: queries once, the per-query candidate
        # block once, the (B, L) index/dist pair out
        hbm = (bq * d + bq * c * d) * b + 2 * bq * l * 4
        return mk(impl="grouped_scan", blocks=(bb, bc), block=None,
                  vmem_bytes=H.scan_footprint(bb, bc, l_pad, d, b),
                  hbm_bytes=hbm)

    def _measure(self, op: str, bshape: tuple, b: int,
                 interpret: bool | None) -> KernelPlan:
        step = self.fold_measured(*bshape[:3], b, interpret=interpret)
        if op == "step":
            return step
        return self._mem[self._key(op, bshape, b)]

    # --- cache plumbing ---------------------------------------------------

    def _store(self, plan: KernelPlan, key: str | None = None) -> None:
        """Memoize ``plan`` under ``key`` — and, for step plans landing on
        their base (un-pinned) key, the derived assign/update plans of the
        same geometry (they share one ``choose_blocks`` run; re-deriving
        them would be a phantom miss). A blk-pinned plan is stored only
        under its pinned key, never over the base entry. Write-through to
        disk."""
        base_key = self._key(plan.op, plan.shape, plan.itemsize)
        if key is None:
            key = base_key
        self._mem[key] = plan
        if key == base_key and plan.op == "step" and plan.block is not None:
            H, d = heuristics, plan.shape[2]
            n, k = plan.shape[0], plan.shape[1]
            cfg = plan.block
            siblings = (
                KernelPlan(op="assign", shape=plan.shape,
                           itemsize=plan.itemsize, hw=plan.hw, impl="flash",
                           blocks=(cfg.assign_block_n, cfg.assign_block_k),
                           block=cfg,
                           vmem_bytes=H.assign_footprint(
                               cfg.assign_block_n, cfg.assign_block_k, d,
                               plan.itemsize),
                           vmem_budget=plan.vmem_budget,
                           hbm_bytes=H.assign_bytes_flash(
                               n, k, d, plan.itemsize),
                           source=plan.source),
                KernelPlan(op="update", shape=plan.shape,
                           itemsize=plan.itemsize, hw=plan.hw,
                           impl="sort_inverse",
                           blocks=(cfg.update_block_n, cfg.update_block_k),
                           block=cfg,
                           vmem_bytes=H.update_footprint(
                               cfg.update_block_n, cfg.update_block_k, d,
                               plan.itemsize),
                           vmem_budget=plan.vmem_budget,
                           hbm_bytes=H.update_bytes_sort_inverse(
                               n, k, d, plan.itemsize),
                           source=plan.source),
            )
            for sib in siblings:
                self._mem[self._key(sib.op, sib.shape, sib.itemsize)] = sib
        self._save()

    def _load_disk(self) -> None:
        if self._disk_loaded or not self.cache_path:
            return
        self._disk_loaded = True
        try:
            with open(self.cache_path, "r", encoding="utf-8") as f:
                raw = json.load(f)
        except (OSError, ValueError):
            return  # missing or corrupt cache: plan from scratch, not fatal
        if not isinstance(raw, dict) or raw.get("version") != CACHE_VERSION:
            return  # stale-version cache: ignored, will be overwritten
        plans = raw.get("plans")
        if not isinstance(plans, dict):
            return
        for key, pd in plans.items():
            try:
                plan = KernelPlan.from_dict(pd)
            except (KeyError, TypeError, ValueError, AttributeError):
                continue  # one bad entry must not poison the rest
            self._disk_raw[key] = pd
            if plan.hw != self.hw.name or key in self._mem:
                continue  # other chips' plans are kept on disk, not used
            self._mem[key] = plan
            self.disk_entries_loaded += 1

    def _save(self) -> None:
        # Called once per *new* plan (a cache miss), so disk traffic is
        # bounded by the number of distinct geometries a process sees —
        # never per dispatch. The write merges over the raw on-disk
        # entries (loaded first if this planner has not read the file
        # yet, e.g. fold_measured as the first call), so plans belonging
        # to other hardware or other sessions are preserved, not erased.
        if not self.cache_path:
            return
        self._load_disk()
        payload = {"version": CACHE_VERSION,
                   "plans": {**self._disk_raw,
                             **{k: p.to_dict() for k, p in self._mem.items()}}}
        try:
            dirname = os.path.dirname(self.cache_path) or "."
            os.makedirs(dirname, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".tmp")
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(payload, f)
            os.replace(tmp, self.cache_path)
        except OSError:
            pass  # read-only FS etc. — persistence is best-effort


# ---------------------------------------------------------------------------
# process-wide default planner
# ---------------------------------------------------------------------------

_DEFAULT: KernelPlanner | None = None


def default_planner() -> KernelPlanner:
    """The process-wide planner every un-parameterized dispatch uses."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = KernelPlanner()
    return _DEFAULT


def set_default_planner(planner: KernelPlanner | None) -> None:
    """Swap the process-wide planner (tests; custom hardware/cache)."""
    global _DEFAULT
    _DEFAULT = planner
