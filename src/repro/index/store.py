"""BucketStore — the one storage layer under FlashIVF posting lists.

Every byte of posting-list payload in the index lives behind this
abstraction; no other module touches a raw bucket tensor (grep-enforced,
like the shard_map rule in ``core/parallel.py``). Two implementations
share one contract:

- ``PaddedBucketStore`` — the historical layout: one capacity-padded
  ``(K, cap, d)`` tensor plus ``(K, cap)`` int32 ids, amortized-doubling
  growth, ``max_cap`` spill budget. Simple, gather-friendly, but
  resident memory scales with ``K * max_cell_size``: one hot cell
  doubles the whole array.

- ``PagedBucketStore`` — vLLM/PagedAttention-style block storage: all
  cells share one flat pool of fixed-size ``(page_size, d)`` pages, each
  cell maps its slots through a per-cell page table of int32 *local*
  page ids, and pages come from a per-shard free-list allocator
  (deterministic: lowest id first). Resident memory scales with
  *occupied* pages (~``n_total / page_size`` plus one partial page per
  non-empty cell), not ``K * max_cell_cap``. Under an optional byte
  budget (``max_bytes``) an LRU evictor frees the coldest cells' pages
  (write-recency clock, bumped per append batch); evicted rows are
  counted per cell (``evict_counts``/``evicted``) the same way
  ``max_cap`` overflow spills are.

Under a K-sharded ``ParallelContext`` each shard owns a contiguous
``pages_per_shard`` slice of the pool (page ids are shard-local, so the
pool partitions over the cells axis with plain ``PartitionSpec``s and
payloads never migrate); local page id 0 of every shard is a reserved
padding page (``_PAD_COORD`` coordinates, ``-1`` ids), which is also
what unmapped page-table entries point at — a gather through the table
can never read stale or foreign data.

Search-side gathers are planner-friendly: ``gather_width`` returns the
per-cell candidate width snapped to a power-of-two bucket of the max
*occupied* cell size (padded: slots; paged: pages), so the jitted search
re-keys only when occupancy crosses a bucket boundary — and the dense
candidate block is capped at what is actually mapped instead of the full
physical capacity.

Snapshots are canonical and mesh-agnostic: ``state_arrays`` serializes
occupied pages packed in cell-major page order (never the raw pool, so a
fragmented free list or a different shard count never leaks into the
artifact), and ``restore_store`` re-allocates them deterministically.
Logical content — per-cell rows in slot order — round-trips exactly, so
restored searches are bitwise-identical.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Array = jax.Array

# Padded-slot coordinate: large enough that a padded candidate can never
# beat a real one, small enough that d * _PAD^2 stays finite in f32 for
# any realistic d (no inf - inf = NaN risk in the crossterm score).
_PAD_COORD = 1e15

STORE_KINDS = ("padded", "paged")


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def _pow2ceil(v: int) -> int:
    return 1 << max(0, int(v) - 1).bit_length()


def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


def _pad_value(dtype):
    """Padding payload for a pool of ``dtype``: the far-away sentinel
    for float payloads; 0 for integer code pools (quantized stores mask
    padding via the zero scale channel, not the coordinate value)."""
    return _PAD_COORD if jnp.dtype(dtype).kind == "f" else 0


def _sublane_min(dtype) -> int:
    """The planner's minimum sublane tile for ``dtype`` (TPU native
    tiling: (8, 128) f32, (16, 128) bf16, (32, 128) int8). Gather-width
    bucketing floors here so a nearly-empty store — e.g. right after
    heavy LRU eviction — never hands the scan a degenerate sub-tile
    candidate width."""
    return max(8, 32 // max(1, jnp.dtype(dtype).itemsize))


def default_store_kind() -> str:
    """The process-wide default backend (``REPRO_BUCKET_STORE`` env)."""
    kind = os.environ.get("REPRO_BUCKET_STORE", "padded").strip().lower()
    if kind not in STORE_KINDS:
        raise ValueError(f"REPRO_BUCKET_STORE={kind!r}: "
                         f"expected one of {STORE_KINDS}")
    return kind


def make_store(kind: str | None, k: int, d: int, dtype, *, capacity: int = 8,
               max_cap: int | None = None, page_size: int | None = None,
               max_bytes: int | None = None, n_shards: int = 1
               ) -> "BucketStore":
    kind = kind or default_store_kind()
    if kind == "padded":
        return PaddedBucketStore(k, d, dtype, capacity=capacity,
                                 max_cap=max_cap)
    if kind == "paged":
        return PagedBucketStore(k, d, dtype, capacity=capacity,
                                max_cap=max_cap,
                                page_size=page_size or 64,
                                max_bytes=max_bytes, n_shards=n_shards)
    raise ValueError(f"unknown bucket store kind {kind!r}")


def restore_store(host: dict, meta: dict, *, k: int, d: int, dtype,
                  n_shards: int = 1) -> "BucketStore":
    """Rebuild a store from snapshot arrays + manifest meta (any mesh).
    Manifests without a ``codec`` key (snapshot v1/v2) are fp32."""
    if meta.get("codec", "fp32") != "fp32":
        return QuantizedBucketStore.restore(host, meta, k=k, d=d,
                                            dtype=dtype, n_shards=n_shards)
    kind = meta.get("kind", "padded")
    if kind == "padded":
        return PaddedBucketStore.restore(host, meta, k=k, d=d, dtype=dtype)
    if kind == "paged":
        return PagedBucketStore.restore(host, meta, k=k, d=d, dtype=dtype,
                                        n_shards=n_shards)
    raise ValueError(f"unknown bucket store kind {kind!r}")


def infer_store_meta(host: dict, meta: dict) -> dict:
    """Best-effort store meta for snapshots whose manifest doesn't cover
    them (an older seqno than the manifest records): scalars re-derived
    from the array shapes, the same contract the padded layout always
    had."""
    if "buckets" in host:
        return {"kind": "padded", "cap": int(host["buckets"].shape[1]),
                "max_cap": meta.get("max_cap"),
                "spilled": int(host["spill_counts"].sum())}
    cell_pages = host["cell_pages"]
    ps = int(host["pool_pages"].shape[1])
    return {"kind": "paged", "page_size": ps,
            "maxp": max(1, int(cell_pages.max()) if cell_pages.size else 1),
            "pps": 0, "n_shards": 1, "max_cap": meta.get("max_cap"),
            "max_bytes": None,
            "spilled": int(host["spill_counts"].sum()),
            "evicted": int(host["evict_counts"].sum()),
            "tick": int(host["last_touch"].max())
            if host["last_touch"].size else 0}


# ---------------------------------------------------------------------------
# jit-side candidate gathers (called from inside the search programs)
# ---------------------------------------------------------------------------

def gather_global(kind: str, arrays, probe: Array, width: int,
                  page_size: int, n_shards: int) -> tuple[Array, Array]:
    """Materialize the probed candidate block on a whole (unsharded)
    store: ``probe (B, nprobe)`` cells -> ``(cand_x (B, nprobe*width, d),
    cand_ids (B, nprobe*width))``. ``width`` slots per cell (a
    ``gather_width`` bucket), so the block is capped at occupied
    capacity, not physical capacity."""
    b, nprobe = probe.shape
    if kind == "padded":
        buckets, bucket_ids = arrays
        d = buckets.shape[-1]
        cand_x = buckets[:, :width][probe].reshape(b, nprobe * width, d)
        cand_ids = bucket_ids[:, :width][probe].reshape(b, nprobe * width)
        return cand_x, cand_ids
    pool, pool_ids, tables = arrays
    d = pool.shape[-1]
    wp = width // page_size
    pps = pool.shape[0] // n_shards
    cps = tables.shape[0] // n_shards
    # shard-local page ids -> global pool rows; unmapped entries are 0 =
    # the owning shard's reserved padding page
    pid = ((probe // cps)[:, :, None] * pps
           + tables[:, :wp][probe]).reshape(b, nprobe * wp)
    cand_x = pool[pid].reshape(b, nprobe * wp * page_size, d)
    cand_ids = pool_ids[pid].reshape(b, nprobe * wp * page_size)
    return cand_x, cand_ids


def gather_cells(kind: str, arrays, cell: Array, width: int,
                 page_size: int) -> tuple[Array, Array]:
    """Shard-local candidate gather inside a shard_map'd search program:
    ``cell (bl, ll)`` holds *local* cell indices with ``k_local`` as the
    not-owned padding cell. Arrays are this shard's owned blocks."""
    bl, ll = cell.shape
    if kind == "padded":
        buckets, bucket_ids = arrays
        k_local, _, d = buckets.shape
        bpad = jnp.concatenate(
            [buckets[:, :width],
             jnp.full((1, width, d), _PAD_COORD, buckets.dtype)], axis=0)
        ipad = jnp.concatenate(
            [bucket_ids[:, :width],
             jnp.full((1, width), -1, jnp.int32)], axis=0)
        return (bpad[cell].reshape(bl, ll * width, d),
                ipad[cell].reshape(bl, ll * width))
    pool, pool_ids, tables = arrays
    d = pool.shape[-1]
    wp = width // page_size
    # the padding cell maps every slot onto local page 0 — this shard's
    # reserved padding page, same as any unmapped table entry
    tpad = jnp.concatenate(
        [tables[:, :wp], jnp.zeros((1, wp), jnp.int32)], axis=0)
    pid = tpad[cell].reshape(bl, ll * wp)
    return (pool[pid].reshape(bl, ll * wp * page_size, d),
            pool_ids[pid].reshape(bl, ll * wp * page_size))


def gather_global_q8(kind: str, arrays, probe: Array, width: int,
                     page_size: int, n_shards: int
                     ) -> tuple[Array, Array, Array]:
    """Quantized-store variant of ``gather_global``: the payload is int8
    codes plus the per-slot f32 scale channel. Returns ``(codes
    (B, nprobe*width, d) int8, scales (B, nprobe*width) f32, ids)``.
    Padding slots carry scale exactly 0.0 — the scan kernel's mask."""
    b, nprobe = probe.shape
    if kind == "padded":
        buckets, bucket_ids, bucket_aux = arrays
        d = buckets.shape[-1]
        return (buckets[:, :width][probe].reshape(b, nprobe * width, d),
                bucket_aux[:, :width][probe].reshape(b, nprobe * width),
                bucket_ids[:, :width][probe].reshape(b, nprobe * width))
    pool, pool_ids, tables, pool_aux = arrays
    d = pool.shape[-1]
    wp = width // page_size
    pps = pool.shape[0] // n_shards
    cps = tables.shape[0] // n_shards
    pid = ((probe // cps)[:, :, None] * pps
           + tables[:, :wp][probe]).reshape(b, nprobe * wp)
    w = nprobe * wp * page_size
    return (pool[pid].reshape(b, w, d), pool_aux[pid].reshape(b, w),
            pool_ids[pid].reshape(b, w))


def gather_cells_q8(kind: str, arrays, cell: Array, width: int,
                    page_size: int) -> tuple[Array, Array, Array]:
    """Quantized-store variant of ``gather_cells`` (shard-local). The
    padding cell ``k_local`` lands on zero-scale slots, so its rows mask
    out of the scan exactly like unmapped pages."""
    bl, ll = cell.shape
    if kind == "padded":
        buckets, bucket_ids, bucket_aux = arrays
        k_local, _, d = buckets.shape
        bpad = jnp.concatenate(
            [buckets[:, :width],
             jnp.zeros((1, width, d), buckets.dtype)], axis=0)
        apad = jnp.concatenate(
            [bucket_aux[:, :width],
             jnp.zeros((1, width), jnp.float32)], axis=0)
        ipad = jnp.concatenate(
            [bucket_ids[:, :width],
             jnp.full((1, width), -1, jnp.int32)], axis=0)
        return (bpad[cell].reshape(bl, ll * width, d),
                apad[cell].reshape(bl, ll * width),
                ipad[cell].reshape(bl, ll * width))
    pool, pool_ids, tables, pool_aux = arrays
    d = pool.shape[-1]
    wp = width // page_size
    tpad = jnp.concatenate(
        [tables[:, :wp], jnp.zeros((1, wp), jnp.int32)], axis=0)
    pid = tpad[cell].reshape(bl, ll * wp)
    w = ll * wp * page_size
    return (pool[pid].reshape(bl, w, d), pool_aux[pid].reshape(bl, w),
            pool_ids[pid].reshape(bl, w))


def list_blocks(kind: str, arrays, seg_list: Array, block_w: int,
                width: int, page_size: int, n_shards: int
                ) -> tuple[Array, Array, int]:
    """The list-major scan's view of a whole (unsharded) fp32 store: where
    each segment's list ``seg_list[s]`` lives. Returns ``(payload,
    blocks, block_w)`` for ``ops.flash_scan_lists``. Padded: ``payload``
    is the ``(K, cap, d)`` tensor, ``blocks`` the lists themselves (a
    list is contiguous rows), read in tiles of ``block_w`` rows (at most
    ``cap``). Paged: a tile is one page, ``blocks (S, W)`` the pool pages
    of each list's first ``W = width / page_size`` table entries."""
    if kind == "padded":
        payload = arrays[0]
        return payload, seg_list, min(block_w, payload.shape[1])
    payload, _, tables = arrays
    pps = payload.shape[0] // n_shards
    cps = tables.shape[0] // n_shards
    lst = seg_list[:, None]
    page = (lst // cps) * pps + tables[lst, jnp.arange(width // page_size)]
    return payload, page, page_size


def slot_ids(kind: str, arrays, lists: Array, slots: Array,
             page_size: int, n_shards: int) -> Array:
    """Global ids of the rows at ``slots`` of ``lists`` (same shapes) on a
    whole (unsharded) store."""
    if kind == "padded":
        return arrays[1][lists, slots]
    pool, pool_ids, tables = arrays[:3]
    pps = pool.shape[0] // n_shards
    cps = tables.shape[0] // n_shards
    pid = (lists // cps) * pps + tables[lists, slots // page_size]
    return pool_ids[pid, slots % page_size]


# ---------------------------------------------------------------------------
# the store contract
# ---------------------------------------------------------------------------

class BucketStore:
    """Shared bookkeeping: counts, spill/evict accounting, the contract
    every consumer layer (index, search programs, placement, snapshots,
    benchmarks) goes through. See the module docstring."""

    kind = "abstract"
    codec_kind = "fp32"     # payload codec (QuantizedBucketStore overrides)

    def __init__(self, k: int, d: int, dtype, *, max_cap: int | None = None):
        self.k, self.d = int(k), int(d)
        self.dtype = jnp.dtype(dtype)
        # memory budget: posting lists never grow past max_cap slots per
        # cell — overflow rows spill (counted, not stored) instead of
        # growing the payload until the device OOMs
        self.max_cap = None if max_cap is None \
            else max(8, _round_up(max_cap, 8))
        self.counts = jnp.zeros((self.k,), jnp.int32)
        self._counts_np = np.zeros(self.k, np.int64)
        self.spilled = 0
        self.evicted = 0
        self.spill_counts = np.zeros(self.k, np.int64)
        self.evict_counts = np.zeros(self.k, np.int64)

    # -- shared helpers ------------------------------------------------

    def _account_spill(self, cells: np.ndarray) -> None:
        self.spill_counts += np.bincount(
            cells, minlength=self.k).astype(np.int64)
        self.spilled += int(cells.size)

    def set_counts(self, v) -> None:
        """Test/repair seam: overwrite the logical list lengths (the
        dead-cell forging used by reliability tests). Payload unchanged."""
        self.counts = jnp.asarray(v, jnp.int32)
        self._counts_np = np.asarray(self.counts).astype(np.int64)

    @property
    def max_count(self) -> int:
        return int(self._counts_np.max()) if self.k else 0

    # -- the contract (implemented by both backends) -------------------

    @property
    def capacity(self) -> int:          # physical slots per cell
        raise NotImplementedError

    @property
    def page_param(self) -> int:        # static gather arg (0 = padded)
        return 0

    @property
    def n_shards(self) -> int:
        return 1

    def append(self, cells: np.ndarray, x_sorted: Array,
               ids: np.ndarray) -> None:
        """Store a CSR-ordered batch: ``cells`` ascending, ``x_sorted``
        the matching rows (device), ``ids`` their global int32 ids. The
        store computes slots, grows/allocates/spills/evicts, and updates
        ``counts``."""
        raise NotImplementedError

    def gather_width(self, min_slots: int = 1) -> int:
        """Per-cell candidate width for the search gather: a power-of-two
        bucket of the max occupied cell size (>= ``min_slots``, clamped
        to physical capacity). This is the plan-cache key dimension."""
        raise NotImplementedError

    def device_arrays(self) -> tuple:
        raise NotImplementedError

    def shard_specs(self, ka) -> tuple:
        raise NotImplementedError

    def dense(self) -> tuple[np.ndarray, np.ndarray]:
        """Host oracle view: ``(x (K, W, d), ids (K, W))`` with padding
        slots at ``_PAD_COORD``/-1 (tests, filtered-brute references)."""
        raise NotImplementedError

    def dense_ids(self) -> Array:
        """Device ``(K, W)`` id view in slot order (posting lists)."""
        raise NotImplementedError

    def flat(self) -> tuple[Array, Array]:
        """Device flattened payload for the brute-force oracle."""
        raise NotImplementedError

    def state_arrays(self) -> dict:
        raise NotImplementedError

    def meta(self) -> dict:
        raise NotImplementedError

    def place(self, pctx) -> None:
        raise NotImplementedError

    def resident_bytes(self) -> int:
        """Device bytes held by the posting-list payload (+ tables)."""
        raise NotImplementedError

    def block_until_ready(self) -> None:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# padded backend (the historical layout, extracted)
# ---------------------------------------------------------------------------

class PaddedBucketStore(BucketStore):
    """One ``(K, cap, d)`` tensor; amortized-doubling growth; ``max_cap``
    spill budget. The JIT-friendly equivalent of CSR — a fixed-shape
    gather target."""

    kind = "padded"

    def __init__(self, k: int, d: int, dtype, *, capacity: int = 8,
                 max_cap: int | None = None, aux: bool = False):
        super().__init__(k, d, dtype, max_cap=max_cap)
        self.cap = max(8, _round_up(int(capacity), 8))
        if self.max_cap is not None:
            self.cap = min(self.cap, self.max_cap)
        self.buckets = jnp.full((self.k, self.cap, self.d),
                                _pad_value(self.dtype), self.dtype)
        self.bucket_ids = jnp.full((self.k, self.cap), -1, jnp.int32)
        # optional per-slot f32 sidecar (codec scales); 0.0 = empty slot
        self.has_aux = bool(aux)
        self.bucket_aux = jnp.zeros((self.k, self.cap), jnp.float32) \
            if self.has_aux else None

    @property
    def capacity(self) -> int:
        return self.cap

    def append(self, cells, x_sorted, ids, aux=None):
        n = int(cells.shape[0])
        if n == 0:
            return
        cells = np.asarray(cells, np.int64)
        ids = np.asarray(ids, np.int32)
        rank = np.arange(n) - np.searchsorted(cells, cells)
        slots = self._counts_np[cells] + rank
        needed = int(slots.max()) + 1
        if needed > self.cap:
            self._grow(needed)
        if needed > self.cap:   # max_cap reached: spill the overflow
            keep = slots < self.cap
            self._account_spill(cells[~keep])
            kj = np.flatnonzero(keep)
            cells, slots, ids = cells[kj], slots[kj], ids[kj]
            kj = jnp.asarray(kj, jnp.int32)
            x_sorted = jnp.take(x_sorted, kj, axis=0)
            if aux is not None:
                aux = jnp.take(aux, kj, axis=0)
        if cells.size:
            cj = jnp.asarray(cells, jnp.int32)
            sj = jnp.asarray(slots, jnp.int32)
            self.buckets = self.buckets.at[cj, sj].set(
                x_sorted.astype(self.dtype))
            self.bucket_ids = self.bucket_ids.at[cj, sj].set(
                jnp.asarray(ids))
            if self.has_aux and aux is not None:
                self.bucket_aux = self.bucket_aux.at[cj, sj].set(
                    jnp.asarray(aux, jnp.float32))
            self._counts_np += np.bincount(
                cells, minlength=self.k).astype(np.int64)
            self.counts = jnp.asarray(self._counts_np, jnp.int32)

    def _grow(self, needed: int) -> None:
        """Amortized doubling, clamped to the ``max_cap`` budget."""
        new_cap = max(_round_up(needed, 8), 2 * self.cap)
        if self.max_cap is not None:
            new_cap = min(new_cap, self.max_cap)
        if new_cap <= self.cap:
            return
        pad = new_cap - self.cap
        self.buckets = jnp.pad(self.buckets, ((0, 0), (0, pad), (0, 0)),
                               constant_values=_pad_value(self.dtype))
        self.bucket_ids = jnp.pad(self.bucket_ids, ((0, 0), (0, pad)),
                                  constant_values=-1)
        if self.has_aux:
            self.bucket_aux = jnp.pad(self.bucket_aux,
                                      ((0, 0), (0, pad)))
        self.cap = new_cap

    def gather_width(self, min_slots: int = 1) -> int:
        sl = _sublane_min(self.dtype)
        w = _pow2ceil(max(sl, self.max_count))
        w = max(w, _round_up(max(1, min_slots), sl))
        return min(self.cap, w)

    def device_arrays(self):
        if self.has_aux:
            return (self.buckets, self.bucket_ids, self.bucket_aux)
        return (self.buckets, self.bucket_ids)

    def shard_specs(self, ka):
        if self.has_aux:
            return (P(ka, None, None), P(ka, None), P(ka, None))
        return (P(ka, None, None), P(ka, None))

    def dense(self):
        return np.asarray(self.buckets), np.asarray(self.bucket_ids)

    def dense_ids(self):
        return self.bucket_ids

    def flat(self):
        return (self.buckets.reshape(self.k * self.cap, self.d),
                self.bucket_ids.reshape(self.k * self.cap))

    def state_arrays(self):
        out = {"buckets": np.asarray(self.buckets),
               "bucket_ids": np.asarray(self.bucket_ids),
               "counts": np.asarray(self.counts),
               "spill_counts": self.spill_counts}
        if self.has_aux:
            out["bucket_aux"] = np.asarray(self.bucket_aux)
        return out

    def meta(self):
        return {"kind": self.kind, "cap": self.cap, "max_cap": self.max_cap,
                "spilled": int(self.spilled)}

    @classmethod
    def restore(cls, host, meta, *, k, d, dtype):
        st = cls(k, d, dtype, capacity=meta["cap"],
                 max_cap=meta.get("max_cap"),
                 aux="bucket_aux" in host)
        assert st.cap == meta["cap"], "capacity rounding drifted"
        st.buckets = jnp.asarray(host["buckets"])
        st.bucket_ids = jnp.asarray(host["bucket_ids"])
        if st.has_aux:
            st.bucket_aux = jnp.asarray(host["bucket_aux"])
        st.counts = jnp.asarray(host["counts"])
        st._counts_np = np.asarray(host["counts"]).astype(np.int64)
        st.spilled = int(meta.get("spilled", host["spill_counts"].sum()))
        st.spill_counts = np.asarray(host["spill_counts"]).copy()
        return st

    def place(self, pctx) -> None:
        ka = pctx.k_axis
        self.buckets = pctx.put(self.buckets, P(ka, None, None))
        self.bucket_ids = pctx.put(self.bucket_ids, P(ka, None))
        if self.has_aux:
            self.bucket_aux = pctx.put(self.bucket_aux, P(ka, None))
        self.counts = pctx.put(self.counts, P(ka))

    def resident_bytes(self) -> int:
        aux = 4 if self.has_aux else 0
        return self.k * self.cap * (self.d * self.dtype.itemsize + 4 + aux)

    def block_until_ready(self) -> None:
        jax.block_until_ready(self.buckets)

    def __repr__(self):
        return (f"PaddedBucketStore(k={self.k}, d={self.d}, "
                f"cap={self.cap})")


# ---------------------------------------------------------------------------
# paged backend (block pool + page tables + free-list allocator + LRU)
# ---------------------------------------------------------------------------

class PagedBucketStore(BucketStore):
    """Fixed-size pages in one flat pool, per-cell page tables, per-shard
    free lists, LRU eviction under ``max_bytes``. See module docstring
    for the layout invariants."""

    kind = "paged"

    def __init__(self, k: int, d: int, dtype, *, capacity: int = 8,
                 max_cap: int | None = None, page_size: int = 64,
                 max_bytes: int | None = None, n_shards: int = 1,
                 aux: bool = False):
        super().__init__(k, d, dtype, max_cap=max_cap)
        self.has_aux = bool(aux)
        self.page_size = max(8, _round_up(int(page_size), 8))
        if k % n_shards:
            raise ValueError(f"k={k} not divisible by n_shards={n_shards}")
        self._n_shards = int(n_shards)
        self.cells_per_shard = self.k // self._n_shards
        self.max_bytes = max_bytes
        # table width (pages per cell) sized for the capacity hint; the
        # pool starts one doubling above the single-hot-cell need
        self.maxp = max(1, _ceil_div(int(capacity), self.page_size))
        if self.max_cap is not None:
            self.maxp = min(self.maxp,
                            max(1, _ceil_div(self.max_cap, self.page_size)))
        pps = max(2, _pow2ceil(1 + self.maxp))
        if self.max_bytes is not None:
            pps = min(pps, max(2, self._budget_pps()))
        self.pps = pps                      # pages per shard (incl. pad)
        self.tables_np = np.zeros((self.k, self.maxp), np.int32)
        self.tables = jnp.asarray(self.tables_np)
        self.pages_np = np.zeros(self.k, np.int32)
        self.last_touch = np.zeros(self.k, np.int64)
        self._tick = 0
        # local page 0 of every shard is the reserved padding page
        self._free = [list(range(1, self.pps))
                      for _ in range(self._n_shards)]
        self.pool = jnp.full(
            (self._n_shards * self.pps, self.page_size, self.d),
            _pad_value(self.dtype), self.dtype)
        self.pool_ids = jnp.full(
            (self._n_shards * self.pps, self.page_size), -1, jnp.int32)
        # optional per-slot f32 sidecar (codec scales); 0.0 = empty slot
        self.pool_aux = jnp.zeros(
            (self._n_shards * self.pps, self.page_size), jnp.float32) \
            if self.has_aux else None

    # -- geometry ------------------------------------------------------

    @property
    def capacity(self) -> int:
        return self.maxp * self.page_size

    @property
    def page_param(self) -> int:
        return self.page_size

    @property
    def n_shards(self) -> int:
        return self._n_shards

    def _page_bytes(self) -> int:
        aux = 4 if self.has_aux else 0
        return self.page_size * (self.d * self.dtype.itemsize + 4 + aux)

    def _budget_pps(self) -> int:
        return int(self.max_bytes
                   // (self._n_shards * self._page_bytes()))

    def _owner(self, cells: np.ndarray) -> np.ndarray:
        return cells // self.cells_per_shard

    # -- allocator -----------------------------------------------------

    def _grow_pool(self, new_pps: int) -> None:
        s, ps, d = self._n_shards, self.page_size, self.d
        self.pool = jnp.pad(
            self.pool.reshape(s, self.pps, ps, d),
            ((0, 0), (0, new_pps - self.pps), (0, 0), (0, 0)),
            constant_values=_pad_value(self.dtype)
            ).reshape(s * new_pps, ps, d)
        self.pool_ids = jnp.pad(
            self.pool_ids.reshape(s, self.pps, ps),
            ((0, 0), (0, new_pps - self.pps), (0, 0)),
            constant_values=-1).reshape(s * new_pps, ps)
        if self.has_aux:
            self.pool_aux = jnp.pad(
                self.pool_aux.reshape(s, self.pps, ps),
                ((0, 0), (0, new_pps - self.pps), (0, 0))
                ).reshape(s * new_pps, ps)
        for sh in range(s):
            self._free[sh].extend(range(self.pps, new_pps))
        self.pps = new_pps

    def _grow_tables(self, need: int) -> None:
        new_maxp = _pow2ceil(max(need, self.maxp + 1))
        if self.max_cap is not None:
            new_maxp = min(new_maxp,
                           max(need, _ceil_div(self.max_cap,
                                               self.page_size)))
        self.tables_np = np.pad(self.tables_np,
                                ((0, 0), (0, new_maxp - self.maxp)))
        self.maxp = new_maxp

    def _evict(self, cell: int) -> None:
        """Free a cold cell's pages back to the allocator: its rows are
        dropped (counted, like spills), its pages reset to padding so the
        flat/brute views never see stale vectors."""
        npg = int(self.pages_np[cell])
        pids = self.tables_np[cell, :npg].tolist()
        sh = cell // self.cells_per_shard
        gp = jnp.asarray([sh * self.pps + p for p in pids], jnp.int32)
        self.pool = self.pool.at[gp].set(_pad_value(self.dtype))
        self.pool_ids = self.pool_ids.at[gp].set(-1)
        if self.has_aux:
            self.pool_aux = self.pool_aux.at[gp].set(0.0)
        lost = int(self._counts_np[cell])
        self.evict_counts[cell] += lost
        self.evicted += lost
        self._counts_np[cell] = 0
        self.pages_np[cell] = 0
        self.tables_np[cell, :] = 0
        self._free[sh] = sorted(self._free[sh] + pids)

    def _alloc(self, shard: int, protect: set) -> int | None:
        """One free page on ``shard`` (lowest id — deterministic), via
        the free list, then pool growth within the byte budget, then LRU
        eviction of cold unprotected cells. ``None`` = truly full."""
        free = self._free[shard]
        if free:
            return free.pop(0)
        new_pps = 2 * self.pps
        if self.max_bytes is not None:
            new_pps = min(new_pps, self._budget_pps())
        if new_pps > self.pps:
            self._grow_pool(new_pps)
            return self._free[shard].pop(0)
        lo = shard * self.cells_per_shard
        hi = lo + self.cells_per_shard
        while not free:
            cand = [c for c in range(lo, hi)
                    if self.pages_np[c] > 0 and c not in protect]
            if not cand:
                return None
            self._evict(min(cand,
                            key=lambda c: (int(self.last_touch[c]), c)))
        return free.pop(0)

    # -- the contract --------------------------------------------------

    def append(self, cells, x_sorted, ids, aux=None):
        n = int(cells.shape[0])
        if n == 0:
            return
        ps = self.page_size
        cells = np.asarray(cells, np.int64)
        ids = np.asarray(ids, np.int32)
        rank = np.arange(n) - np.searchsorted(cells, cells)
        slots = self._counts_np[cells] + rank
        if self.max_cap is not None:     # same budget rule as padded
            over = slots >= self.max_cap
            if over.any():
                self._account_spill(cells[over])
                kj = np.flatnonzero(~over)
                cells, slots, ids = cells[kj], slots[kj], ids[kj]
                kj = jnp.asarray(kj, jnp.int32)
                x_sorted = jnp.take(x_sorted, kj, axis=0)
                if aux is not None:
                    aux = jnp.take(aux, kj, axis=0)
        ucells, ustart = np.unique(cells, return_index=True)
        uend = np.r_[ustart[1:], cells.size] - 1
        umax = slots[uend] if cells.size else np.zeros(0, np.int64)
        protect = set(int(c) for c in ucells)
        drop_from = {}                   # cell -> first unstorable slot
        for c, smax in zip(ucells, umax):
            c, need = int(c), int(smax) // ps + 1
            if need > self.maxp:
                self._grow_tables(need)
            for p in range(int(self.pages_np[c]), need):
                pid = self._alloc(c // self.cells_per_shard, protect)
                if pid is None:          # budget truly exhausted
                    drop_from[c] = p * ps
                    break
                self.tables_np[c, p] = pid
                self.pages_np[c] = p + 1
        if drop_from:
            thr = np.full(self.k, np.iinfo(np.int64).max)
            for c, t in drop_from.items():
                thr[c] = t
            over = slots >= thr[cells]
            self._account_spill(cells[over])
            kj = np.flatnonzero(~over)
            cells, slots, ids = cells[kj], slots[kj], ids[kj]
            kj = jnp.asarray(kj, jnp.int32)
            x_sorted = jnp.take(x_sorted, kj, axis=0)
            if aux is not None:
                aux = jnp.take(aux, kj, axis=0)
        if cells.size:
            gpid = (self._owner(cells) * self.pps
                    + self.tables_np[cells, slots // ps])
            gj = jnp.asarray(gpid, jnp.int32)
            sj = jnp.asarray(slots % ps, jnp.int32)
            self.pool = self.pool.at[gj, sj].set(x_sorted.astype(self.dtype))
            self.pool_ids = self.pool_ids.at[gj, sj].set(jnp.asarray(ids))
            if self.has_aux and aux is not None:
                self.pool_aux = self.pool_aux.at[gj, sj].set(
                    jnp.asarray(aux, jnp.float32))
            self._counts_np += np.bincount(
                cells, minlength=self.k).astype(np.int64)
        if ucells.size:                  # write-recency LRU clock
            self._tick += 1
            self.last_touch[ucells] = self._tick
        self.counts = jnp.asarray(self._counts_np, jnp.int32)
        self.tables = jnp.asarray(self.tables_np)

    def gather_width(self, min_slots: int = 1) -> int:
        wp = _pow2ceil(max(1, int(self.pages_np.max()) if self.k else 1))
        wp = max(wp, _ceil_div(max(_sublane_min(self.dtype), min_slots),
                               self.page_size))
        return min(wp, self.maxp) * self.page_size

    def device_arrays(self):
        if self.has_aux:
            return (self.pool, self.pool_ids, self.tables, self.pool_aux)
        return (self.pool, self.pool_ids, self.tables)

    def shard_specs(self, ka):
        if self.has_aux:
            return (P(ka, None, None), P(ka, None), P(ka, None),
                    P(ka, None))
        return (P(ka, None, None), P(ka, None), P(ka, None))

    def _global_pids_np(self) -> np.ndarray:
        owner = np.arange(self.k) // self.cells_per_shard
        return owner[:, None] * self.pps + self.tables_np

    def dense(self):
        gp = self._global_pids_np().reshape(-1)
        w = self.maxp * self.page_size
        x = np.asarray(self.pool)[gp].reshape(self.k, w, self.d)
        ids = np.asarray(self.pool_ids)[gp].reshape(self.k, w)
        return x, ids

    def dense_ids(self):
        gp = jnp.asarray(self._global_pids_np().reshape(-1), jnp.int32)
        return self.pool_ids[gp].reshape(self.k, self.maxp * self.page_size)

    def flat(self):
        # pad pages carry _PAD_COORD/-1: safe to scan wholesale
        return (self.pool.reshape(-1, self.d), self.pool_ids.reshape(-1))

    def state_arrays(self):
        # canonical packed form: occupied pages in cell-major page order
        # (physical page ids / free-list fragmentation never serialize)
        gp = []
        for c in range(self.k):
            sh = c // self.cells_per_shard
            gp.extend(sh * self.pps + int(p)
                      for p in self.tables_np[c, :int(self.pages_np[c])])
        gp = np.asarray(gp, np.int64)
        pool_np = np.asarray(self.pool)
        ids_np = np.asarray(self.pool_ids)
        out = {"pool_pages": pool_np[gp] if gp.size
               else pool_np[:0],
               "pool_page_ids": ids_np[gp] if gp.size else ids_np[:0],
               "cell_pages": self.pages_np.astype(np.int32),
               "counts": np.asarray(self.counts),
               "last_touch": self.last_touch.copy(),
               "spill_counts": self.spill_counts,
               "evict_counts": self.evict_counts}
        if self.has_aux:
            aux_np = np.asarray(self.pool_aux)
            out["pool_page_aux"] = aux_np[gp] if gp.size else aux_np[:0]
        return out

    def meta(self):
        return {"kind": self.kind, "page_size": self.page_size,
                "pps": self.pps, "maxp": self.maxp,
                "n_shards": self._n_shards, "max_cap": self.max_cap,
                "max_bytes": self.max_bytes, "spilled": int(self.spilled),
                "evicted": int(self.evicted), "tick": int(self._tick)}

    @classmethod
    def restore(cls, host, meta, *, k, d, dtype, n_shards=1):
        ps = int(meta["page_size"])
        st = cls(k, d, dtype, capacity=ps, page_size=ps,
                 max_cap=meta.get("max_cap"),
                 max_bytes=meta.get("max_bytes"), n_shards=n_shards,
                 aux="pool_page_aux" in host)
        st.maxp = max(1, int(meta["maxp"]))
        st.tables_np = np.zeros((k, st.maxp), np.int32)
        cell_pages = np.asarray(host["cell_pages"], np.int64)
        cps = st.cells_per_shard
        shard_used = np.asarray(
            [cell_pages[s * cps:(s + 1) * cps].sum() + 1
             for s in range(n_shards)])
        if n_shards == meta.get("n_shards") and meta.get("pps"):
            pps = max(int(meta["pps"]), int(shard_used.max()))
        else:   # different mesh: deterministic canonical sizing
            pps = max(2, _pow2ceil(int(shard_used.max())))
        st.pps = pps
        st._free = [list(range(1, pps)) for _ in range(n_shards)]
        np_dt = np.dtype(st.dtype.name)
        pool_np = np.full((n_shards * pps, ps, d),
                          _pad_value(st.dtype), np_dt)
        ids_np = np.full((n_shards * pps, ps), -1, np.int32)
        aux_np = np.zeros((n_shards * pps, ps), np.float32) \
            if st.has_aux else None
        pages, page_ids = host["pool_pages"], host["pool_page_ids"]
        u = 0
        for c in range(k):
            sh = c // cps
            for p in range(int(cell_pages[c])):
                pid = st._free[sh].pop(0)
                st.tables_np[c, p] = pid
                pool_np[sh * pps + pid] = pages[u]
                ids_np[sh * pps + pid] = page_ids[u]
                if aux_np is not None:
                    aux_np[sh * pps + pid] = host["pool_page_aux"][u]
                u += 1
        st.pool = jnp.asarray(pool_np)
        st.pool_ids = jnp.asarray(ids_np)
        if st.has_aux:
            st.pool_aux = jnp.asarray(aux_np)
        st.tables = jnp.asarray(st.tables_np)
        st.pages_np = cell_pages.astype(np.int32)
        st.counts = jnp.asarray(host["counts"], jnp.int32)
        st._counts_np = np.asarray(host["counts"]).astype(np.int64)
        st.last_touch = np.asarray(host["last_touch"]).copy()
        st._tick = int(meta.get("tick", st.last_touch.max(initial=0)))
        st.spilled = int(meta.get("spilled", host["spill_counts"].sum()))
        st.spill_counts = np.asarray(host["spill_counts"]).copy()
        st.evicted = int(meta.get("evicted",
                                  host["evict_counts"].sum()))
        st.evict_counts = np.asarray(host["evict_counts"]).copy()
        return st

    def place(self, pctx) -> None:
        ka = pctx.k_axis
        self.pool = pctx.put(self.pool, P(ka, None, None))
        self.pool_ids = pctx.put(self.pool_ids, P(ka, None))
        self.tables = pctx.put(self.tables, P(ka, None))
        if self.has_aux:
            self.pool_aux = pctx.put(self.pool_aux, P(ka, None))
        self.counts = pctx.put(self.counts, P(ka))

    def resident_bytes(self) -> int:
        return (self._n_shards * self.pps * self._page_bytes()
                + self.k * self.maxp * 4)

    def occupied_pages(self) -> int:
        return int(self.pages_np.sum())

    def block_until_ready(self) -> None:
        jax.block_until_ready(self.pool)

    def __repr__(self):
        return (f"PagedBucketStore(k={self.k}, d={self.d}, "
                f"page_size={self.page_size}, pages={self.occupied_pages()}"
                f"/{self._n_shards * self.pps}, evicted={self.evicted})")


# ---------------------------------------------------------------------------
# quantized payloads: rescore reservoir + codec wrapper
# ---------------------------------------------------------------------------

class RescoreReservoir:
    """Host-side full-precision row pool keyed by global id — the exact
    half of two-phase search. The quantized scan proposes top-``R``
    candidate ids; the verify phase looks their original f32 rows up
    here (``O(b·R·d)``, never whole buckets). FIFO ring under an
    optional byte budget: when full, the oldest rows fall out and those
    candidates rescore from their decoded codes instead — recall
    degrades gracefully, nothing breaks."""

    def __init__(self, d: int, *, max_bytes: int | None = None):
        self.d = int(d)
        self.max_bytes = max_bytes
        cap = self._cap_rows()
        n0 = 0 if cap is None else cap
        self._rows = np.zeros((n0, self.d), np.float32)
        self._ids = np.full(n0, -1, np.int64)    # id held per row
        self._id2row = np.full(1024, -1, np.int64)
        self._cursor = 0
        self.evicted = 0

    def _cap_rows(self) -> int | None:
        if self.max_bytes is None:
            return None
        return max(1, int(self.max_bytes) // (4 * self.d + 8))

    def __len__(self) -> int:
        return int((self._ids >= 0).sum())

    def resident_bytes(self) -> int:
        return self._rows.shape[0] * (4 * self.d + 8)

    def _ensure_index(self, max_id: int) -> None:
        if max_id >= self._id2row.size:
            grown = np.full(_pow2ceil(max_id + 1), -1, np.int64)
            grown[:self._id2row.size] = self._id2row
            self._id2row = grown

    def put(self, ids, x) -> None:
        ids = np.asarray(ids, np.int64).reshape(-1)
        x = np.asarray(x, np.float32).reshape(-1, self.d)
        if ids.size == 0:
            return
        self._ensure_index(int(ids.max()))
        row = self._id2row[ids]
        have = row >= 0
        if have.any():                      # refresh in place
            self._rows[row[have]] = x[have]
        new_ids, new_x = ids[~have], x[~have]
        if new_ids.size == 0:
            return
        cap = self._cap_rows()
        if cap is None:                     # unbounded: plain append
            base = self._rows.shape[0]
            self._rows = np.concatenate([self._rows, new_x])
            self._ids = np.concatenate([self._ids, new_ids])
            self._id2row[new_ids] = base + np.arange(new_ids.size)
            return
        if new_ids.size > cap:              # batch larger than the ring
            self.evicted += new_ids.size - cap
            new_ids, new_x = new_ids[-cap:], new_x[-cap:]
        pos = (self._cursor + np.arange(new_ids.size)) % cap
        old = self._ids[pos]
        dropped = old[old >= 0]
        self._id2row[dropped] = -1
        self.evicted += int(dropped.size)
        self._rows[pos] = new_x
        self._ids[pos] = new_ids
        self._id2row[new_ids] = pos
        self._cursor = int((self._cursor + new_ids.size) % cap)

    def lookup(self, ids) -> tuple[np.ndarray, np.ndarray]:
        """``ids`` any-shape int -> (rows ``ids.shape + (d,)`` f32,
        found bool). Missing / negative ids return zero rows."""
        ids = np.asarray(ids, np.int64)
        safe = np.clip(ids, 0, self._id2row.size - 1)
        row = np.where((ids >= 0) & (ids < self._id2row.size),
                       self._id2row[safe], -1)
        found = row >= 0
        out = np.zeros(ids.shape + (self.d,), np.float32)
        out[found] = self._rows[row[found]]
        return out, found

    def state_arrays(self) -> dict:
        """Occupied rows packed oldest-first (ring order), so a restore
        rebuilds identical FIFO behavior."""
        cap = self._cap_rows()
        if cap is None:
            keep = self._ids >= 0
            return {"rescore_rows": self._rows[keep],
                    "rescore_ids": self._ids[keep]}
        order = (self._cursor + np.arange(cap)) % cap
        order = order[self._ids[order] >= 0]
        return {"rescore_rows": self._rows[order],
                "rescore_ids": self._ids[order]}

    @classmethod
    def restore(cls, host, d: int, *, max_bytes=None) -> "RescoreReservoir":
        res = cls(d, max_bytes=max_bytes)
        res.put(host["rescore_ids"], host["rescore_rows"])
        res.evicted = 0
        return res


class QuantizedBucketStore(BucketStore):
    """Codec wrapper over either backend: the inner store holds int8
    codes (its payload dtype is the codec's) plus the per-slot f32
    scale sidecar; ids, page tables, the allocator/evictor, and the
    canonical snapshot logic are the inner store's, untouched. The
    wrapper owns the *anchors* — the cell centroids frozen at encode
    time (``refresh`` moves the live routing centroids; decoding stays
    against what the codes were built from) — and the optional
    ``RescoreReservoir``. ``kind`` stays the inner backend's name (the
    codec is an orthogonal axis, reported via ``codec_kind``)."""

    def __init__(self, inner: BucketStore, codec, anchors, *,
                 reservoir: RescoreReservoir | None = None,
                 cache=None, logical_dtype=jnp.float32):
        # deliberately no super().__init__: all bookkeeping delegates
        self._inner = inner
        self.codec = codec
        self.anchors = jnp.asarray(anchors, jnp.float32)
        self.reservoir = reservoir
        self.cache = cache          # DeviceRescoreCache | None
        self.dtype = jnp.dtype(logical_dtype)   # what consumers feed us
        self.k, self.d = inner.k, inner.d

    # -- delegated bookkeeping ----------------------------------------

    @property
    def kind(self) -> str:
        return self._inner.kind

    @property
    def codec_kind(self) -> str:
        return self.codec.kind

    @property
    def counts(self):
        return self._inner.counts

    def set_counts(self, v) -> None:
        self._inner.set_counts(v)

    @property
    def max_count(self) -> int:
        return self._inner.max_count

    @property
    def max_cap(self):
        return self._inner.max_cap

    @property
    def spilled(self) -> int:
        return self._inner.spilled

    @spilled.setter
    def spilled(self, v) -> None:
        self._inner.spilled = v

    @property
    def spill_counts(self):
        return self._inner.spill_counts

    @spill_counts.setter
    def spill_counts(self, v) -> None:
        self._inner.spill_counts = v

    @property
    def evicted(self) -> int:
        return self._inner.evicted

    @property
    def evict_counts(self):
        return self._inner.evict_counts

    @property
    def capacity(self) -> int:
        return self._inner.capacity

    @property
    def page_param(self) -> int:
        return self._inner.page_param

    @property
    def n_shards(self) -> int:
        return self._inner.n_shards

    def gather_width(self, min_slots: int = 1) -> int:
        return self._inner.gather_width(min_slots)

    def __getattr__(self, name):
        # anything else (page_size, occupied_pages, maxp, ...) is the
        # inner store's business
        return getattr(self._inner, name)

    # -- the contract --------------------------------------------------

    def append(self, cells, x_sorted, ids):
        if int(np.asarray(cells).shape[0]) == 0:
            return
        cj = jnp.asarray(np.asarray(cells), jnp.int32)
        anchor_rows = jnp.take(self.anchors, cj, axis=0)
        codes, scales = self.codec.encode(
            jnp.asarray(x_sorted, jnp.float32), anchor_rows)
        if self.reservoir is not None:
            self.reservoir.put(np.asarray(ids),
                               np.asarray(x_sorted, np.float32))
        if self.cache is not None:
            shard = None
            if self.cache.shards > 1:
                shard = np.asarray(cells) // (self.k // self.cache.shards)
            self.cache.put(ids, jnp.asarray(x_sorted, jnp.float32),
                           shard=shard)
        self._inner.append(cells, codes, ids, aux=scales)

    def device_arrays(self):
        return (*self._inner.device_arrays(), self.anchors)

    def shard_specs(self, ka):
        return (*self._inner.shard_specs(ka), P(ka, None))

    def cache_arrays(self):
        """The device rescore cache's (keys, rows) — extra operands of
        the fused propose->lookup->rescore programs (never part of
        ``device_arrays``: the fp32/host paths don't carry them)."""
        return self.cache.device_arrays()

    def _rewarm_cache(self) -> None:
        """Re-warm the device cache from the durable host tier after a
        restore: walk the posting lists (an id's home cell — and thus
        its K-shard — is fixed at append time) and insert every row the
        reservoir still holds. Cell-major, so the resident set under a
        byte budget is deterministic given the snapshot."""
        if self.cache is None or self.reservoir is None:
            return
        ids = np.asarray(self._inner.dense_ids())
        cells = np.broadcast_to(
            np.arange(self.k, dtype=np.int64)[:, None], ids.shape)
        valid = ids >= 0
        ids_v, cells_v = ids[valid], cells[valid]
        rows, found = self.reservoir.lookup(ids_v)
        shard = None
        if self.cache.shards > 1:
            shard = cells_v[found] // (self.k // self.cache.shards)
        self.cache.put(ids_v[found], rows[found], shard=shard)

    def _dense_aux(self) -> np.ndarray:
        inner = self._inner
        if inner.kind == "padded":
            return np.asarray(inner.bucket_aux)
        gp = inner._global_pids_np().reshape(-1)
        return np.asarray(inner.pool_aux)[gp].reshape(
            self.k, inner.maxp * inner.page_size)

    def dense(self):
        """Decoded f32 oracle view, with reservoir rows (the exact
        originals) overlaid where present — the same rows two-phase
        rescore scores, so brute-vs-two-phase parity is exact."""
        codes, ids = self._inner.dense()
        aux = self._dense_aux()
        x = np.asarray(self.anchors)[:, None, :] \
            + codes.astype(np.float32) * aux[..., None]
        if self.reservoir is not None:
            rows, found = self.reservoir.lookup(ids)
            x = np.where(found[..., None], rows, x)
        x[ids < 0] = _PAD_COORD
        return x.astype(np.float32), ids

    def dense_ids(self):
        return self._inner.dense_ids()

    def flat(self):
        x, ids = self.dense()
        return (jnp.asarray(x.reshape(-1, self.d)),
                jnp.asarray(ids.reshape(-1)))

    def state_arrays(self):
        out = self._inner.state_arrays()
        out["anchors"] = np.asarray(self.anchors)
        if self.reservoir is not None:
            out.update(self.reservoir.state_arrays())
        return out

    def meta(self):
        return dict(self._inner.meta(), codec=self.codec.kind,
                    reservoir=self.reservoir is not None,
                    rescore_bytes=None if self.reservoir is None
                    else self.reservoir.max_bytes,
                    rescore_cache=None if self.cache is None
                    else self.cache.meta())

    @classmethod
    def restore(cls, host, meta, *, k, d, dtype, n_shards=1):
        from repro.index.quant import make_codec
        from repro.index.rescore_cache import (DeviceRescoreCache,
                                               default_rescore_kind)
        codec = make_codec(meta["codec"])
        kind = meta.get("kind", "padded")
        if kind == "padded":
            inner = PaddedBucketStore.restore(host, meta, k=k, d=d,
                                              dtype=codec.pool_dtype)
        else:
            inner = PagedBucketStore.restore(host, meta, k=k, d=d,
                                             dtype=codec.pool_dtype,
                                             n_shards=n_shards)
        reservoir = None
        if meta.get("reservoir") and "rescore_ids" in host:
            reservoir = RescoreReservoir.restore(
                host, d, max_bytes=meta.get("rescore_bytes"))
        # snapshot v5 manifests record the cache axis explicitly; older
        # ones predate it and adopt the process default. The pool never
        # serializes — geometry rebuilds for the *current* mesh and the
        # reservoir (the durable tier) re-warms it below.
        if "rescore_cache" in meta:
            cmeta = meta["rescore_cache"]
            cache = None if cmeta is None else DeviceRescoreCache(
                d, max_bytes=cmeta.get("max_bytes"),
                ways=cmeta.get("ways", 4), shards=n_shards)
        elif default_rescore_kind() == "device":
            cache = DeviceRescoreCache(d, max_bytes=meta.get(
                "rescore_bytes"), shards=n_shards)
        else:
            cache = None
        st = cls(inner, codec, host["anchors"], reservoir=reservoir,
                 cache=cache, logical_dtype=dtype)
        st._rewarm_cache()
        return st

    def place(self, pctx) -> None:
        self._inner.place(pctx)
        self.anchors = pctx.put(self.anchors, P(pctx.k_axis, None))
        if self.cache is not None:
            self.cache.place(pctx)

    def resident_bytes(self) -> int:
        extra = 0 if self.cache is None else self.cache.resident_bytes()
        return self._inner.resident_bytes() + self.k * self.d * 4 + extra

    def payload_bytes(self) -> int:
        """Device bytes of codes+ids(+scales) alone — the apples-to-
        apples ~0.25x comparison against an fp32 store's payload."""
        return self._inner.resident_bytes()

    def block_until_ready(self) -> None:
        self._inner.block_until_ready()

    def __repr__(self):
        res = len(self.reservoir) if self.reservoir is not None else 0
        return (f"QuantizedBucketStore(codec={self.codec.kind}, "
                f"inner={self._inner!r}, reservoir_rows={res}, "
                f"cache={self.cache!r})")


def make_quantized_store(kind: str | None, k: int, d: int, dtype, *,
                         anchors, codec: str = "q8", capacity: int = 8,
                         max_cap: int | None = None,
                         page_size: int | None = None,
                         max_bytes: int | None = None, n_shards: int = 1,
                         rescore_bytes: int | None = None,
                         reservoir: bool = True,
                         rescore: str | None = None
                         ) -> QuantizedBucketStore:
    """Codec-wrapped store: like ``make_store`` but the payload pool
    holds codec codes (+ per-slot scale sidecar), with an optional
    byte-budgeted full-precision rescore reservoir (``reservoir=False``
    falls back to decoded-code rescoring). ``rescore`` picks the phase-2
    row source: ``"device"`` (a :class:`DeviceRescoreCache` sharing the
    ``rescore_bytes`` budget — zero host transfers on the search path)
    or ``"host"`` (the reservoir round trip, the parity oracle);
    ``None`` defers to ``REPRO_RESCORE`` (default device)."""
    from repro.index.quant import make_codec
    from repro.index.rescore_cache import (RESCORE_KINDS,
                                           DeviceRescoreCache,
                                           default_rescore_kind)
    cdc = make_codec(codec)
    kind = kind or default_store_kind()
    if kind == "padded":
        inner = PaddedBucketStore(k, d, cdc.pool_dtype, capacity=capacity,
                                  max_cap=max_cap, aux=True)
    elif kind == "paged":
        inner = PagedBucketStore(k, d, cdc.pool_dtype, capacity=capacity,
                                 max_cap=max_cap,
                                 page_size=page_size or 64,
                                 max_bytes=max_bytes, n_shards=n_shards,
                                 aux=True)
    else:
        raise ValueError(f"unknown bucket store kind {kind!r}")
    res = RescoreReservoir(d, max_bytes=rescore_bytes) if reservoir \
        else None
    rescore = rescore or default_rescore_kind()
    if rescore not in RESCORE_KINDS:
        raise ValueError(
            f"unknown rescore kind {rescore!r}: expected {RESCORE_KINDS}")
    cache = DeviceRescoreCache(d, max_bytes=rescore_bytes,
                               shards=n_shards) \
        if rescore == "device" else None
    return QuantizedBucketStore(inner, cdc, anchors, reservoir=res,
                                cache=cache, logical_dtype=dtype)
