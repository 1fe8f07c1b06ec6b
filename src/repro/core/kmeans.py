"""flash-kmeans public API: exact Lloyd iterations on the fused kernels.

``KMeans`` is the composable module: configure once, then ``fit`` (full
Lloyd loop under ``lax.while_loop``), ``iterate`` (single step — the online
primitive used inside models), or ``fit_batched`` (vmapped B independent
problems, the paper's batch axis). Given a mesh (``KMeans(cfg, mesh)``)
it runs ``fit``, ``iterate`` and ``predict`` across the mesh's devices
through ``core.parallel.ParallelContext``: points sharded along N, one
psum of the statistics per step.

The math is byte-for-byte Lloyd's algorithm — no approximation anywhere
(paper's "mathematically exact" contract); only the dataflow differs by
``assign_impl`` / ``update_impl``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import plan as _plan
from repro.core.init import init_centroids
from repro.kernels import ops, ref
from repro.kernels.ops import BlockConfig

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class KMeansConfig:
    k: int
    max_iters: int = 25
    tol: float = 0.0                  # centroid-shift^2 tolerance (0 = run all iters)
    init: str = "random"              # random | kmeans++
    assign_impl: str = "flash"        # flash | ref
    update_impl: str = "sort_inverse" # sort_inverse | scatter | dense_onehot | fused
    step_impl: str = "auto"           # auto | fused | two_pass
    block: BlockConfig | None = None  # None -> KernelPlanner plan
    interpret: bool | None = None     # None -> auto (CPU interpret, TPU compiled)
    dtype: jnp.dtype | None = None    # compute dtype override for x/c
    # planning layer override (None -> the process-wide default planner);
    # excluded from eq/hash so configs stay comparable/jit-closable
    planner: "_plan.KernelPlanner | None" = dataclasses.field(
        default=None, compare=False, repr=False)

    def _planner(self) -> "_plan.KernelPlanner":
        return self.planner if self.planner is not None \
            else _plan.default_planner()

    def blocks_for(self, n: int, d: int, dtype_bytes: int) -> BlockConfig:
        if self.block is not None:
            return self.block
        return self._planner().block_config(n, self.k, d, dtype_bytes)

    def resolved_step_impl(self, n: int, d: int, dtype_bytes: int,
                           blk: BlockConfig | None = None) -> str:
        """'fused' (single FlashLloyd pass) or 'two_pass' (assign+update).

        ``step_impl="auto"`` applies the VMEM + roofline crossover rule —
        the ``KernelPlanner``'s cached step plan — judged at the block
        shapes that will actually be launched (``blk`` if given, else
        ``self.block``, else the plan's own) — but only on the flash +
        sort_inverse fast path;
        explicitly requested reference impls are honoured so baselines
        stay comparable. ``update_impl="fused"`` is an alias for
        ``step_impl="fused"``; either spelling combined with
        ``step_impl="two_pass"``, a non-flash ``assign_impl``, or a
        reference ``update_impl`` is contradictory and raises.
        """
        if self.update_impl == "fused" or self.step_impl == "fused":
            if self.step_impl == "two_pass":
                raise ValueError(
                    "update_impl='fused' contradicts step_impl='two_pass'")
            if self.assign_impl != "flash":
                raise ValueError(
                    "the fused step subsumes the assignment; it cannot "
                    f"be combined with assign_impl={self.assign_impl!r}")
            if self.update_impl not in ("fused", "sort_inverse"):
                raise ValueError(
                    "step_impl='fused' contradicts "
                    f"update_impl={self.update_impl!r}")
            return "fused"
        if self.step_impl == "two_pass":
            return "two_pass"
        if self.step_impl != "auto":
            raise ValueError(f"unknown step impl {self.step_impl!r}")
        if self.assign_impl != "flash" or self.update_impl != "sort_inverse":
            return "two_pass"
        return self._planner().step_impl(
            n, self.k, d, dtype_bytes,
            blk=blk if blk is not None else self.block)

    def stats_only_update_impl(self) -> str:
        """Update impl for a stats-only pass over *given* assignments.

        The fused step computes statistics jointly with its own argmin
        sweep, so it has no stats-only form; fused-configured cfgs fall
        back to the sort-inverse kernel (used by the K-sharded
        distributed update and the masked streaming batch).
        """
        if self.update_impl == "fused" or self.step_impl == "fused":
            return "sort_inverse"
        return self.update_impl


class KMeansState(NamedTuple):
    centroids: Array       # (K, d)
    assignments: Array     # (N,) int32
    inertia: Array         # () f32 — sum of min squared distances
    iteration: Array       # () int32
    shift: Array           # () f32 — squared centroid movement of last step


def _assign(x: Array, c: Array, cfg: KMeansConfig, blk: BlockConfig
            ) -> tuple[Array, Array]:
    if cfg.assign_impl == "flash":
        return ops.flash_assign(x, c, block_n=blk.assign_block_n,
                                block_k=blk.assign_block_k,
                                interpret=cfg.interpret)
    if cfg.assign_impl == "ref":
        return ref.assign_ref(x, c)
    raise ValueError(f"unknown assign impl {cfg.assign_impl!r}")


def lloyd_stats(x: Array, c: Array, cfg: KMeansConfig,
                blk: BlockConfig | None = None
                ) -> tuple[Array, Array, Array, Array]:
    """One iteration's sufficient statistics: (a, sums, counts, inertia).

    Dispatches between the fused single-pass FlashLloyd kernel (one HBM
    stream of ``x``) and the two-pass assign + update pipeline according
    to ``cfg.resolved_step_impl`` — identical math either way, only the
    dataflow differs. Shared by ``lloyd_step`` and the chunked driver.
    """
    if blk is None:
        blk = cfg.blocks_for(x.shape[0], x.shape[1], x.dtype.itemsize)
    impl = cfg.resolved_step_impl(x.shape[0], x.shape[1], x.dtype.itemsize,
                                  blk=blk)
    if impl == "fused":
        with jax.named_scope("lloyd.fused"):
            return ops.flash_lloyd_step(
                x, c, block_n=blk.fused_block_n, block_k=blk.fused_block_k,
                interpret=cfg.interpret)
    with jax.named_scope("lloyd.assign"):
        a, m = _assign(x, c, cfg, blk)
        inertia = jnp.sum(m)
    with jax.named_scope("lloyd.update"):
        s, cnt = ops.centroid_stats(
            x, a, k=cfg.k, impl=cfg.update_impl, block_n=blk.update_block_n,
            block_k=blk.update_block_k, interpret=cfg.interpret)
    return a, s, cnt, inertia


def lloyd_step(x: Array, c: Array, cfg: KMeansConfig,
               blk: BlockConfig | None = None
               ) -> tuple[Array, Array, Array]:
    """One exact Lloyd iteration. Returns (c_new, assignments, inertia)."""
    a, s, cnt, inertia = lloyd_stats(x, c, cfg, blk)
    with jax.named_scope("lloyd.finalize"):
        return ops.finalize_centroids(s, cnt, c), a, inertia


def make_kmeans_fn(cfg: KMeansConfig):
    """Build a jittable ``fit(key, x) -> KMeansState`` for a fixed config."""

    def fit(key: Array, x: Array) -> KMeansState:
        if cfg.dtype is not None:
            x = x.astype(cfg.dtype)
        n, d = x.shape
        blk = cfg.blocks_for(n, d, x.dtype.itemsize)
        c0 = init_centroids(key, x, cfg.k, cfg.init)

        def cond(st: KMeansState):
            return jnp.logical_and(st.iteration < cfg.max_iters,
                                   st.shift > cfg.tol)

        def body(st: KMeansState):
            c_new, a, inertia = lloyd_step(x, st.centroids, cfg, blk)
            shift = jnp.sum(
                (c_new.astype(jnp.float32)
                 - st.centroids.astype(jnp.float32)) ** 2)
            return KMeansState(c_new, a, inertia, st.iteration + 1, shift)

        st0 = KMeansState(
            centroids=c0,
            assignments=jnp.zeros((n,), jnp.int32),
            inertia=jnp.array(jnp.inf, jnp.float32),
            iteration=jnp.array(0, jnp.int32),
            shift=jnp.array(jnp.inf, jnp.float32),
        )
        return jax.lax.while_loop(cond, body, st0)

    return fit


class KMeans:
    """Composable exact k-means module (the paper's contribution as an op).

    >>> km = KMeans(KMeansConfig(k=64, max_iters=10))
    >>> state = km.fit(jax.random.PRNGKey(0), x)          # (N, d)
    >>> states = km.fit_batched(key, xb)                  # (B, N, d)
    >>> c1, a, j = km.iterate(x, c0)                      # online single step

    With ``mesh`` the points are sharded ``P(data_axes, None)`` over the
    mesh's data axes (``ParallelContext.for_mesh``; a host array or an
    array placed otherwise is placed so, and N must divide by the data
    shards) and the centroids replicated, or sharded over a cells axis
    of size above 1. ``iterate`` is one shard_map'd Lloyd step: per-shard
    statistics (fused or two-pass, as the planner picks at the per-shard
    shape), one psum of (sums, counts, inertia), a replicated update; it
    returns the assignments sharded like ``x``. ``fit`` runs the same step
    in the sharded loop from the sharded random init, which takes each
    chosen row on the device that holds it. ``fit_batched`` and the
    k-means++ init run on one device only.
    """

    def __init__(self, cfg: KMeansConfig, mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        if mesh is None:
            self._fit = jax.jit(make_kmeans_fn(cfg))
            self._fit_batched = jax.jit(jax.vmap(make_kmeans_fn(cfg)))
            self._step = jax.jit(functools.partial(lloyd_step, cfg=cfg))
            return
        from repro.core.parallel import ParallelContext
        self.pctx = pctx = ParallelContext.for_mesh(mesh)
        self._step = pctx.make_step(cfg)
        self._loop = pctx.make_kmeans_fit(cfg)
        self._init = pctx.make_random_init(cfg.k)
        self._assign = pctx.make_assign(cfg)
        self._x_sharding = jax.sharding.NamedSharding(mesh, pctx.data_spec)
        self._c_sharding = jax.sharding.NamedSharding(mesh,
                                                      pctx.centroid_spec)

    def _place(self, x: Array, sharding) -> Array:
        """``x`` as ``sharding`` lays it out, moved only where it is not."""
        if (isinstance(x, jax.Array)
                and x.sharding.is_equivalent_to(sharding, x.ndim)):
            return x
        return jax.device_put(x, sharding)

    def _points(self, x: Array) -> Array:
        if self.mesh is None:
            return self._cast(x)
        shards = self.pctx.n_data_shards
        if x.shape[0] % shards:
            raise ValueError(f"N={x.shape[0]} must divide by the mesh's "
                             f"{shards} data shards")
        return self._cast(self._place(x, self._x_sharding))

    def _centroids(self, c: Array) -> Array:
        if self.mesh is None:
            return self._cast(c)
        return self._cast(self._place(c, self._c_sharding))

    def fit(self, key: Array, x: Array) -> KMeansState:
        if self.mesh is None:
            return self._fit(key, x)
        if self.cfg.init != "random":
            raise NotImplementedError(
                f"init={self.cfg.init!r} runs on one device; with a mesh "
                "the init is 'random'")
        x = self._points(x)
        return self._loop(x, self._centroids(self._init(key, x)))

    def fit_batched(self, key: Array, x: Array) -> KMeansState:
        if self.mesh is not None:
            raise NotImplementedError("fit_batched runs on one device")
        b = x.shape[0]
        keys = jax.random.split(key, b)
        return self._fit_batched(keys, x)

    def _cast(self, x: Array) -> Array:
        """Apply ``cfg.dtype`` exactly as ``fit`` does, so every entry
        point computes distances in the same precision (a dtype override
        must not make ``predict`` disagree with fit-time assignments)."""
        return x if self.cfg.dtype is None else x.astype(self.cfg.dtype)

    def iterate(self, x: Array, c: Array) -> tuple[Array, Array, Array]:
        if self.mesh is not None and obs.enabled():
            obs.count("lloyd.sharded_steps")
            obs.count("lloyd.allreduce_bytes", self.pctx.collective_bytes(
                "stats_psum", k=self.cfg.k, d=x.shape[1]))
        return self._step(self._points(x), self._centroids(c))

    def predict(self, x: Array, c: Array) -> Array:
        x, c = self._points(x), self._centroids(c)
        if self.mesh is not None:
            return self._assign(x, c)[0]
        blk = self.cfg.blocks_for(x.shape[0], x.shape[1], x.dtype.itemsize)
        return _assign(x, c, self.cfg, blk)[0]
