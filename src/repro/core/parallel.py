"""ParallelContext — the one shard_map execution layer of flash-kmeans.

Every multi-device program in this repo — the distributed Lloyd step
and loop (``KMeans(cfg, mesh)``, the entry point; ``core.distributed``
is a thin adapter), the data-parallel streaming ``partial_fit``
(core.streaming), and the sharded FlashIVF build/search/add pipeline
(index.ivf) — is built from the same four collective primitives, and this
module is the only place that calls ``shard_map``.

Each sharding mode has one Lloyd step body (``_n_sharded_step``: points
sharded, centroids replicated; ``_k_sharded_step``: centroids sharded
too), and both the single step (``make_step``) and the ``while_loop`` fit
(``make_kmeans_fit``) run it. The step's psums sit under the scope
``lloyd.allreduce``, beside the kernels' ``lloyd.fused`` /
``lloyd.assign`` / ``lloyd.update`` and the update's ``lloyd.finalize``.
The primitives:

- **stats psum-tree** (``psum_stats`` / ``owned_stats``): per-shard
  ``SufficientStats`` are reduced with one ``psum`` over the data axes —
  O(K·d) collective bytes per round, independent of N (the
  communication-avoiding structure of linear-algebraic k-means: keep the
  O(N·d) work local, exchange only the O(K·d) reduction).
- **two-stage assignment** (``two_stage_assign``): with centroids
  partitioned over ``k_axis``, each shard computes a local argmin over
  its owned centroids, then the per-shard ``(value, index)`` minima are
  merged across shards — O(N_local · P_k) bytes, never the (N, K)
  distance matrix. Ties break toward the lower *global* centroid id
  (``jax.lax.top_k`` parity with the single-device kernels), because
  centroid ownership is contiguous in rank order and the merge prefers
  the lower concatenation index.
- **top-L merge** (``merge_topl``): the generalization used by sharded
  IVF search — per-shard candidate lists ``(B, L_loc)`` are gathered and
  reduced to the global ascending top-L, O(B · L_loc) bytes per shard.
- **logical axes**: meshes name physical axes (``data``/``model``/
  ``pod``); k-means programs speak the logical axes ``"points"`` (data
  parallelism over N) and ``"cells"`` (centroid/posting-list
  parallelism over K), resolved through ``utils.sharding`` rules by
  ``ParallelContext.for_mesh``.

KernelPlanner interaction: every kernel dispatch inside a shard_map body
resolves its blocks at the *traced per-shard shape* (``cfg.blocks_for``
on the local N / local K), so plans stay correct under partitioning —
one cached plan per shard geometry, not per global shape.

The collective-bytes model (``collective_bytes``) mirrors the HBM-bytes
models in ``core.heuristics``: a closed-form per-shard wire-byte count
for each primitive, used by DESIGN.md and the regression tests that pin
sharded search traffic to O(b·L).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import init as _init
from repro.core import kmeans as _km
from repro.core.kmeans import KMeansConfig
from repro.core.streaming import SufficientStats
from repro.kernels import ops
from repro.utils import sharding as shu

Array = jax.Array


# ---------------------------------------------------------------------------
# mesh construction — the one helper every launcher builds meshes through
# ---------------------------------------------------------------------------

def build_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """The single mesh constructor of the repo.

    ``launch.mesh`` (production / host factories), ``launch.train``,
    ``launch.serve --mesh`` and the tests all route here, so device
    enumeration and axis naming happen in exactly one place.
    """
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} and axes {axes} disagree")
    # Auto axes: shardings propagate through XLA as the shard_map
    # programs and the host-side glue around them expect (jax >= 0.9
    # defaults make_mesh to Explicit axes, sharding in types)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return build_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = max(1, min(data, n))
    model = max(1, min(model, n // max(data, 1)))
    return build_mesh((data, model), ("data", "model"))


def parse_mesh_flag(flag: str) -> Mesh:
    """Parse a ``--mesh`` CLI flag into a host mesh.

    ``"8"`` -> 8-way data parallelism; ``"2x4"`` -> 2 data shards x 4
    cell shards (physical axes ``data`` x ``model``; the k-means logical
    axes ``points``/``cells`` resolve onto them via ``utils.sharding``).
    """
    parts = [int(p) for p in flag.lower().replace("*", "x").split("x")]
    if len(parts) == 1:
        parts = [parts[0], 1]
    if len(parts) != 2 or any(p < 1 for p in parts):
        raise ValueError(f"--mesh expects 'DATA' or 'DATAxCELLS', got {flag!r}")
    return build_mesh(parts, ("data", "model"))


def _fit_cond(cfg: KMeansConfig):
    """The Lloyd-loop stopping rule, shared with ``make_kmeans_fn``:
    carry tail is ``(..., iteration, shift)``."""
    def cond(carry):
        it, shift = carry[-2], carry[-1]
        return jnp.logical_and(it < cfg.max_iters, shift > cfg.tol)
    return cond


# ---------------------------------------------------------------------------
# ParallelContext
# ---------------------------------------------------------------------------

class ParallelContext:
    """One mesh + axis assignment = one k-means execution substrate.

    >>> mesh = build_mesh((2, 4), ("data", "model"))
    >>> pctx = ParallelContext(mesh, data_axes=("data",), k_axis="model")
    >>> fit = pctx.make_kmeans_fit(cfg)          # distributed Lloyd loop
    >>> lloyd = pctx.make_step(cfg)              # one Lloyd step
    >>> step = pctx.make_partial_fit(cfg)        # streaming mini-batch
    >>> assign = pctx.make_assign(cfg)           # two-stage argmin

    ``data_axes`` shard points (N); ``k_axis`` (optional) shards
    centroids and posting lists (K). Collective primitives
    (``psum_stats``, ``two_stage_assign``, ``merge_topl``,
    ``owned_stats``) must be called from inside a shard_map body built by
    this context; the ``make_*`` builders assemble complete jitted
    programs around them.
    """

    def __init__(self, mesh: Mesh, data_axes: Sequence[str] = ("data",),
                 k_axis: str | None = None):
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        missing = [a for a in self.data_axes if a not in mesh.axis_names]
        if missing or not self.data_axes:
            # fail loudly: silently dropping a typo'd axis would run the
            # job un-distributed over the intended dimension
            raise ValueError(f"data_axes {missing or tuple(data_axes)} not "
                             f"in mesh axes {mesh.axis_names} "
                             "(for_mesh resolves logical axes instead)")
        if k_axis is not None and k_axis not in mesh.axis_names:
            raise ValueError(f"k_axis={k_axis!r} not in mesh axes "
                             f"{mesh.axis_names}")
        if k_axis in self.data_axes:
            raise ValueError(f"k_axis={k_axis!r} overlaps data_axes")
        self.k_axis = k_axis

    @classmethod
    def for_mesh(cls, mesh: Mesh, rules: dict | None = None
                 ) -> "ParallelContext":
        """Resolve the k-means logical axes onto ``mesh``.

        ``"points"`` maps to the data-parallel physical axes and
        ``"cells"`` to the centroid axis, per ``utils.sharding`` rules; a
        size-1 cells axis degrades to no K-sharding (two-stage machinery
        is pure overhead at P_k = 1).
        """
        rules = rules or shu.rules_for_mesh(mesh)
        data_axes = tuple(a for a in rules.get("points", ())
                          if a in mesh.axis_names)
        cand = tuple(a for a in rules.get("cells", ())
                     if a in mesh.axis_names and a not in data_axes)
        k_axis = cand[0] if cand and mesh.shape[cand[0]] > 1 else None
        return cls(mesh, data_axes=data_axes or mesh.axis_names[:1],
                   k_axis=k_axis)

    # -- shard-count / spec helpers ----------------------------------------

    @property
    def n_data_shards(self) -> int:
        out = 1
        for a in self.data_axes:
            out *= self.mesh.shape[a]
        return out

    @property
    def n_k_shards(self) -> int:
        return self.mesh.shape[self.k_axis] if self.k_axis else 1

    def k_local(self, k: int) -> int:
        pk = self.n_k_shards
        if k % pk != 0:
            raise ValueError(f"K={k} must divide the {pk}-way k_axis")
        return k // pk

    @property
    def data_spec(self) -> P:
        return P(self.data_axes, None)

    @property
    def centroid_spec(self) -> P:
        return P(self.k_axis, None) if self.k_axis else P(None, None)

    def spmd(self, f, in_specs, out_specs):
        """Build a per-shard SPMD program over this mesh (shard_map
        under the hood — the only entry point drivers use, so the raw
        mechanism never leaks outside this module). Replication checking
        is off: pallas_call outputs carry no vma information."""
        return jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def put(self, x, spec: P):
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def shard_points(self, x) -> Array:
        """Place a host array onto the mesh, sharded along N."""
        return self.put(x, self.data_spec)

    def shard_centroids(self, c) -> Array:
        return self.put(c, self.centroid_spec)

    def replicate(self, x) -> Array:
        return self.put(x, P(*([None] * jnp.ndim(x))))

    def pad_points(self, x, value=0) -> tuple[Array, Array, int]:
        """Pad N up to a data-shard multiple; returns (x_pad, mask, n).

        The mask excludes the padding rows from every statistics
        reduction (the ragged-last-shard guard: a shard made entirely of
        padding contributes exactly-zero stats, never NaN).
        """
        x = jnp.asarray(x)
        n = x.shape[0]
        mult = self.n_data_shards
        n_pad = ((n + mult - 1) // mult) * mult
        if n_pad != n:
            x = jnp.pad(x, ((0, n_pad - n),) + ((0, 0),) * (x.ndim - 1),
                        constant_values=value)
        mask = jnp.arange(n_pad) < n
        return x, mask, n

    # -- collective primitives (inside shard_map bodies only) --------------

    def psum_stats(self, stats: SufficientStats,
                   axes: Sequence[str] | None = None) -> SufficientStats:
        """The O(K·d) sufficient-statistics reduction tree."""
        axes = tuple(axes) if axes is not None else self.data_axes
        if not axes:
            return stats
        return SufficientStats(*jax.lax.psum(tuple(stats), axes))

    def merge_topl(self, idx: Array, val: Array, l: int, *,
                   axis: str | None = None, tie: Array | None = None,
                   valid: Array | None = None) -> tuple[Array, Array]:
        """Cross-shard ascending top-``l`` merge of per-shard candidates.

        ``idx``/``val``: (B, L_loc) per-shard lists, each already
        ascending. Gathers O(B · L_loc) bytes per shard — never the
        candidate payloads — and reduces to the global (B, l).

        Without ``tie``, equal values break toward the lower
        (shard-rank, local-rank) pair — i.e. toward the lower global id
        when ownership is rank-contiguous and local lists are id-ordered
        on ties (``top_k`` parity; exact for the two-stage argmin and
        the probe merge). When shard rank does *not* encode the
        single-device ordering — the sharded IVF result merge, whose
        reference orders candidates by global probe rank — pass ``tie``
        (B, L_loc) int32: equal values then break toward the lower tie
        key (lexicographic (val, tie) sort), reproducing the reference
        selection exactly on ties.

        ``valid`` (scalar bool, per shard): a shard passing ``False``
        contributes nothing — its list is blanked to ``(inf, -1)`` (and
        tie-key int32 max) *before* the gather, so the merge behaves as
        if the shard were absent. This is the dead-shard seam of the
        reliability layer: a failed replica degrades the result pool
        honestly instead of poisoning it.
        """
        axis = axis if axis is not None else self.k_axis
        if valid is not None:
            val = jnp.where(valid, val, jnp.inf)
            idx = jnp.where(valid, idx, -1)
            if tie is not None:
                tie = jnp.where(valid, tie, jnp.iinfo(jnp.int32).max)
        if axis is None:
            return idx[:, :l], val[:, :l]
        b = val.shape[0]

        def cat(arr):
            gathered = jax.lax.all_gather(arr, axis)     # (P, B, L_loc)
            return jnp.moveaxis(gathered, 0, 1).reshape(b, -1)

        v_cat, i_cat = cat(val), cat(idx)
        t_cat = cat(tie) if tie is not None else None
        if v_cat.shape[1] < l:   # degenerate global pool: pad honestly
            pad = l - v_cat.shape[1]
            v_cat = jnp.pad(v_cat, ((0, 0), (0, pad)),
                            constant_values=jnp.inf)
            i_cat = jnp.pad(i_cat, ((0, 0), (0, pad)), constant_values=-1)
            if t_cat is not None:
                t_cat = jnp.pad(t_cat, ((0, 0), (0, pad)),
                                constant_values=jnp.iinfo(jnp.int32).max)
        if t_cat is None:
            neg_v, pos = jax.lax.top_k(-v_cat, l)
            return jnp.take_along_axis(i_cat, pos, axis=1), -neg_v
        pos = jnp.lexsort((t_cat, v_cat), axis=-1)[:, :l]
        return (jnp.take_along_axis(i_cat, pos, axis=1),
                jnp.take_along_axis(v_cat, pos, axis=1))

    def two_stage_assign(self, x: Array, c_local: Array, cfg: KMeansConfig
                         ) -> tuple[Array, Array]:
        """Global argmin with centroids sharded over ``k_axis``.

        Stage 1: local argmin over the owned centroid shard (the same
        FlashAssign kernel as single-device, planned at the per-shard
        shape). Stage 2: cross-shard (value, index) min-merge. Matches
        single-device ``flash_assign`` bitwise, including ties toward
        the lower global centroid id.
        """
        blk = cfg.blocks_for(x.shape[0], x.shape[1], x.dtype.itemsize)
        a_loc, m_loc = _km._assign(x, c_local.astype(x.dtype), cfg, blk)
        if self.k_axis is None:
            return a_loc, m_loc
        lo = jax.lax.axis_index(self.k_axis) * c_local.shape[0]
        gi, gv = self.merge_topl((a_loc + lo)[:, None], m_loc[:, None], 1)
        return gi[:, 0].astype(jnp.int32), gv[:, 0]

    def owned_stats(self, x: Array, a_glob: Array, k: int, cfg: KMeansConfig,
                    mask: Array | None = None) -> tuple[Array, Array]:
        """Per-shard centroid statistics for the owned centroid range,
        psum'd over the data axes.

        Returns ``(sums (k_owned, d) f32, counts (k_owned,) f32)`` where
        ``k_owned = k / P_k`` (all of ``k`` without a k_axis). Points
        outside the owned range — and masked (padding) rows — are
        remapped to a dummy bucket that is sliced off, so the update is
        K-parallel with zero duplication and a shard owning only dead
        cells reduces to exact zeros (its centroids are then kept as-is
        by ``finalize_centroids``, never divided by zero).
        """
        blk = cfg.blocks_for(x.shape[0], x.shape[1], x.dtype.itemsize)
        if self.k_axis is None:
            ok = mask if mask is not None else None
            if ok is None:
                a_eff, k_eff = a_glob, k
            else:
                a_eff = jnp.where(ok, a_glob, k).astype(jnp.int32)
                k_eff = k + 1
        else:
            kl = self.k_local(k)
            lo = jax.lax.axis_index(self.k_axis) * kl
            rel = a_glob - lo
            ok = jnp.logical_and(rel >= 0, rel < kl)
            if mask is not None:
                ok = jnp.logical_and(ok, mask)
            a_eff = jnp.where(ok, rel, kl).astype(jnp.int32)
            k_eff, k = kl + 1, kl
        s, n = ops.centroid_stats(
            x, a_eff, k=k_eff, impl=cfg.stats_only_update_impl(),
            block_n=blk.update_block_n, block_k=blk.update_block_k,
            interpret=cfg.interpret)
        with jax.named_scope("lloyd.allreduce"):
            return jax.lax.psum((s[:k], n[:k]), self.data_axes)

    # -- program builders ---------------------------------------------------

    def make_assign(self, cfg: KMeansConfig):
        """Jitted global assignment: ``(x_sharded, c) -> (a, min_sq_d)``.

        ``x`` sharded over the data axes; ``c`` replicated (or sharded
        ``P(k_axis, None)`` under K-sharding, where the two-stage
        argmin + (val, idx) min-merge runs).
        """
        def shard_fn(x, c):
            return self.two_stage_assign(x, c, cfg)

        fn = self.spmd(
            shard_fn,
            in_specs=(self.data_spec, self.centroid_spec),
            out_specs=(P(self.data_axes), P(self.data_axes)))
        return jax.jit(fn)

    def make_kmeans_fit(self, cfg: KMeansConfig,
                        compress_pod_axis: str | None = None,
                        masked: bool = False):
        """Build the distributed Lloyd loop for this context.

        Returns ``fit(x_sharded, c0) -> KMeansState`` — or
        ``fit(x_sharded, mask_sharded, c0)`` with ``masked=True`` (ragged
        N padded to a shard multiple; padding rows are excluded from
        statistics and inertia). The loop runs the sharding mode's step
        body (the one ``make_step`` runs once) entirely inside one
        shard_map'd program: one collective round per iteration (O(K·d)
        psum — plus, under K-sharding, the O(N_local · P_k) assignment
        merge), under the same
        ``while (iter < max_iters and shift > tol)`` early-stop rule as
        the single-device fit (the shift is replicated — a scalar psum
        over the cells axis under K-sharding — so every shard exits on
        the same iteration).

        ``compress_pod_axis``: hierarchical reduction — full-precision
        psum inside each pod, then error-feedback int8 exchange of the
        (K, d) statistics across the (slow) pod axis. 8x wire-byte
        reduction on the cross-pod links; EF keeps the iteration
        asymptotically exact.
        """
        if self.k_axis is None:
            return self._make_fit_n_sharded(cfg, compress_pod_axis, masked)
        if compress_pod_axis is not None:
            raise NotImplementedError(
                "compressed pod reduction is not supported together with "
                "K-sharding")
        return self._make_fit_k_sharded(cfg, masked)

    # -- Lloyd step bodies: one per sharding mode, shared by the loop -------
    # (``make_kmeans_fit``) and the single step (``make_step``)

    def _n_sharded_step(self, cfg: KMeansConfig, x: Array, c: Array,
                        mask: Array | None = None, err=None,
                        compress_pod_axis: str | None = None):
        """One Lloyd step with points sharded and centroids replicated
        (inside a shard_map body): per-shard statistics (fused or
        two-pass, planned at the per-shard shape), one psum of (sums,
        counts, inertia) over the data axes, a replicated update.

        ``err``: the error-feedback residuals ``(err_s, err_n)`` of the
        compressed cross-pod exchange (``compress_pod_axis``), carried
        by the loop; returned updated (unchanged without compression).
        Returns ``(c_new, assignments, inertia, err)``.
        """
        batch, a = SufficientStats.from_batch(x, c, cfg, mask=mask)
        with jax.named_scope("lloyd.allreduce"):
            if compress_pod_axis is None:
                batch = self.psum_stats(batch)
            else:
                from repro.optim import compression
                intra = tuple(ax for ax in self.data_axes
                              if ax != compress_pod_axis)
                s, n = jax.lax.psum((batch.sums, batch.counts), intra)
                s, err_s = compression.ef_quantized_allreduce(
                    s, err[0], compress_pod_axis)
                n, err_n = compression.ef_quantized_allreduce(
                    n, err[1], compress_pod_axis)
                batch = SufficientStats(
                    s, n, jax.lax.psum(batch.inertia, self.data_axes))
                err = (err_s, err_n)
        with jax.named_scope("lloyd.finalize"):
            return batch.finalize(c), a, batch.inertia, err

    def _k_sharded_step(self, cfg: KMeansConfig, x: Array, c_local: Array,
                        mask: Array | None = None):
        """One Lloyd step with points and centroids sharded (inside a
        shard_map body): the two-stage argmin, the owned statistics
        psum'd over the data axes, the update of the owned centroid
        slice. Returns ``(c_local_new, global assignments, inertia)``."""
        a_glob, m_glob = self.two_stage_assign(x, c_local, cfg)
        j = jnp.sum(jnp.where(mask, m_glob, 0.0) if mask is not None
                    else m_glob)
        with jax.named_scope("lloyd.allreduce"):
            inertia = jax.lax.psum(j, self.data_axes)
        s, n = self.owned_stats(x, a_glob, cfg.k, cfg, mask=mask)
        with jax.named_scope("lloyd.finalize"):
            c_new = ops.finalize_centroids(s, n, c_local)
        return c_new, a_glob.astype(jnp.int32), inertia

    def make_step(self, cfg: KMeansConfig):
        """Jitted single Lloyd step ``(x_sharded, c) -> (c_new,
        assignments, inertia)``: the loop's step body run once, the
        online primitive ``KMeans(cfg, mesh).iterate`` dispatches. ``c``
        and ``c_new`` are replicated (sharded ``P(k_axis, None)`` under
        K-sharding); the assignments are sharded like ``x``."""
        if self.k_axis is None:
            def shard_fn(x, c):
                return self._n_sharded_step(cfg, x, c)[:3]
        else:
            self.k_local(cfg.k)

            def shard_fn(x, c):
                return self._k_sharded_step(cfg, x, c)
        c_spec = self.centroid_spec
        return jax.jit(self.spmd(shard_fn,
                                 in_specs=(self.data_spec, c_spec),
                                 out_specs=(c_spec, P(self.data_axes), P())))

    def make_random_init(self, k: int):
        """Jitted ``(key, x_sharded) -> c0``: the ``k`` rows ``random_init``
        picks from the same key, replicated. Each shard takes the chosen
        rows it owns (zeros elsewhere) and one psum of (k, d) combines
        them, so no device holds more of ``x`` than its own shard."""
        axes = self.data_axes

        def shard_fn(key, x):
            idx = _init.random_indices(key, x.shape[0] * self.n_data_shards,
                                       k)
            lo = jax.lax.axis_index(axes) * x.shape[0]
            return jax.lax.psum(_init.owned_rows(x, idx, lo), axes)

        return jax.jit(self.spmd(shard_fn, in_specs=(P(), self.data_spec),
                                 out_specs=P(None, None)))

    def _make_fit_n_sharded(self, cfg: KMeansConfig,
                            compress_pod_axis: str | None, masked: bool):
        def shard_fn(x, mask, c0):
            def body(carry):
                c, _, _, err_s, err_n, it, _ = carry
                c_new, a, inertia, (err_s, err_n) = self._n_sharded_step(
                    cfg, x, c, mask if masked else None, (err_s, err_n),
                    compress_pod_axis)
                shift = jnp.sum((c_new.astype(jnp.float32)
                                 - c.astype(jnp.float32)) ** 2)
                return c_new, a, inertia, err_s, err_n, it + 1, shift

            zero_s = jnp.zeros((cfg.k, x.shape[1]), jnp.float32)
            zero_n = jnp.zeros((cfg.k,), jnp.float32)
            c, a, inertia, _, _, it, shift = jax.lax.while_loop(
                _fit_cond(cfg), body,
                (c0, jnp.zeros((x.shape[0],), jnp.int32),
                 jnp.array(jnp.inf, jnp.float32), zero_s, zero_n,
                 jnp.array(0, jnp.int32), jnp.array(jnp.inf, jnp.float32)))
            return c, a, inertia, it, shift

        return self._finish_fit(shard_fn, masked, k_sharded=False)

    def _make_fit_k_sharded(self, cfg: KMeansConfig, masked: bool):
        self.k_local(cfg.k)

        def shard_fn(x, mask, c0_local):
            def body(carry):
                c_local, _, _, it, _ = carry
                c_new, a, inertia = self._k_sharded_step(
                    cfg, x, c_local, mask if masked else None)
                # global centroid shift: local slice + psum over cells
                shift = jax.lax.psum(
                    jnp.sum((c_new.astype(jnp.float32)
                             - c_local.astype(jnp.float32)) ** 2),
                    self.k_axis)
                return c_new, a, inertia, it + 1, shift

            return jax.lax.while_loop(
                _fit_cond(cfg), body,
                (c0_local, jnp.zeros((x.shape[0],), jnp.int32),
                 jnp.array(jnp.inf, jnp.float32), jnp.array(0, jnp.int32),
                 jnp.array(jnp.inf, jnp.float32)))

        return self._finish_fit(shard_fn, masked, k_sharded=True)

    def _finish_fit(self, shard_fn, masked: bool, k_sharded: bool):
        c_spec = P(self.k_axis, None) if k_sharded else P(None, None)
        in_specs = (self.data_spec, P(self.data_axes), c_spec)
        out_specs = (c_spec, P(self.data_axes), P(), P(), P())
        jitted = jax.jit(self.spmd(shard_fn, in_specs=in_specs,
                                   out_specs=out_specs))
        if masked:
            def fit(x, mask, c0):
                return _km.KMeansState(*jitted(x, mask, c0))
        else:
            # unmasked callers keep the fit(x, c0) signature; the dummy
            # mask is never read
            def fit(x, c0):
                return _km.KMeansState(
                    *jitted(x, jnp.ones((x.shape[0],), jnp.bool_), c0))
        return fit

    def make_partial_fit(self, cfg: KMeansConfig, *, decay: float = 1.0,
                         local_iters: int = 1):
        """Data-parallel streaming step, the shard_map'd twin of
        ``streaming.partial_fit_step``.

        Returns ``step(x_pad, mask, c, sums, counts, inertia) ->
        (c', sums', counts', inertia', a, batch_inertia)``: per-shard
        masked batch statistics, **one O(K·d) psum per mini-batch**, a
        replicated M-step. The running stats stay replicated, so the
        marginal collective cost of staying clustered is independent of
        both the stream length and the batch size.
        """
        axes = self.data_axes

        def shard_fn(x, mask, c, sums, counts, inertia):
            base = SufficientStats(sums, counts, inertia).scale(decay)
            merged, a, batch = base, None, None
            for _ in range(max(1, local_iters)):
                batch, a = SufficientStats.from_batch(x, c, cfg, mask=mask)
                batch = self.psum_stats(batch, axes)
                merged = base.merge(batch)
                c = merged.finalize(c)
            return (c, merged.sums, merged.counts, merged.inertia, a,
                    batch.inertia)

        fn = self.spmd(
            shard_fn,
            in_specs=(self.data_spec, P(self.data_axes), P(None, None),
                      P(None, None), P(None), P()),
            out_specs=(P(None, None), P(None, None), P(None), P(),
                       P(self.data_axes), P()))
        return jax.jit(fn)

    # -- collective-bytes model (see DESIGN.md, "Parallel layer") ----------

    def collective_bytes(self, op: str, *, k: int = 0, d: int = 0,
                         n_local: int = 0, b: int = 0, l: int = 0) -> int:
        """Modeled per-shard wire bytes of one collective round.

        - ``stats_psum``:    2·4·(K·d + K + 1)          (O(K·d), N-free)
        - ``assign_merge``:  2·4·N_local·P_k            (val+idx gather)
        - ``topl_merge``:    2·4·b·l·P_k                (O(b·L), payload-free)

        The factor 2 counts the (value, index) pair; f32/int32 = 4 bytes.
        All models are *received* bytes per shard for the all_gather
        based merges and round-trip bytes for the psum tree — the same
        altitude as the HBM models in ``core.heuristics``: exact enough
        to rank designs, simple enough to assert in tests.
        """
        if op == "stats_psum":
            return 2 * 4 * (k * d + k + 1)
        if op == "assign_merge":
            return 2 * 4 * n_local * self.n_k_shards
        if op == "topl_merge":
            return 2 * 4 * b * l * self.n_k_shards
        raise ValueError(f"unknown collective op {op!r}")

    def search_collective_bytes(self, b: int, nprobe: int, topk: int,
                                k: int, cap: int = 0, d: int = 0) -> int:
        """Per-batch cross-shard traffic of sharded IVF search.

        Two top-L merges — the probe merge at L = min(nprobe, K/P_k) and
        the result merge at L = min(topk, candidate pool) — and nothing
        else: posting-list payloads (``cap``, ``d``) never cross shards,
        which is the whole point (and what the regression test pins:
        the model must be independent of ``cap``/``d``/``n``).
        """
        del cap, d  # documented non-dependence
        return search_collective_bytes_model(b, nprobe, topk, k,
                                             self.n_k_shards)

    def describe(self) -> str:
        return (f"ParallelContext(mesh={dict(self.mesh.shape)}, "
                f"points={self.data_axes}, "
                f"cells={self.k_axis or '-'}x{self.n_k_shards})")

    __repr__ = describe


def search_collective_bytes_model(b: int, nprobe: int, topk: int, k: int,
                                  p_k: int) -> int:
    """Closed-form wire model of sharded IVF search for a hypothetical
    ``p_k``-way cells partition (the benchmark uses this to report the
    modeled traffic even on a single-device run): one probe merge at
    ``L = min(nprobe, K/p_k)`` plus one result merge at ``L = topk``,
    each a (value, index) all_gather of ``2·4·b·L·p_k`` bytes/shard."""
    if p_k <= 1:
        return 0
    ll = min(nprobe, max(1, k // p_k))
    return 2 * 4 * b * (ll + topk) * p_k
