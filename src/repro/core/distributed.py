"""Distributed flash-kmeans — the thin adapter over ``core.parallel``.

The entry point of a multi-device fit is ``KMeans(cfg, mesh)``
(``fit``, ``iterate``, ``predict``), which resolves a
``ParallelContext.for_mesh(mesh)`` — the single execution layer every
multi-device program (distributed Lloyd, streaming ``partial_fit``,
sharded FlashIVF) is built on. This adapter keeps the older surface:

- ``make_distributed_kmeans(mesh, cfg, data_axes, k_axis,
  compress_pod_axis)`` — builds a ``ParallelContext`` with explicit
  axes and returns its jitted Lloyd loop ``fit(x_sharded, c0) ->
  (centroids, assignments, inertia)``;
- ``shard_points`` — host-array placement along the data axes.

The centroid statistics ``(s_k, n_k)`` are *sufficient statistics* and
associative, so the out-of-core chunk reduction (core.chunked), the
streaming accumulator (core.streaming), the data-parallel multi-chip
reduction here, and the multi-pod reduction are all the same tree:

  per-shard Lloyd statistics  ->  psum over data axes  ->  replicated
  ``finalize_centroids`` update.

Two sharding modes compose, each with one Lloyd step body in
``ParallelContext`` that the loop and the single step (``make_step``)
both run:

- **N-sharding** (``data_axes``): points sharded; centroids replicated.
  One psum of (K, d) + (K,) + () per iteration — collective bytes are
  O(K d), independent of N. The fused single-pass FlashLloyd kernel
  runs distributed exactly as it does on one chip.
- **K-sharding** (``k_axis``): centroids sharded too (very large K).
  The argmin runs in two stages (``ParallelContext.two_stage_assign``):
  local argmin over the owned centroid shard, then a cross-shard
  (value, index) min-merge — O(N_local · P_k) bytes, still ≪
  materializing D. Update statistics are computed only for the owned
  centroid range (``ParallelContext.owned_stats``). The fused kernel
  cannot apply here (the global assignment is only known after the
  merge); a fused-configured ``cfg`` transparently uses the
  sort-inverse statistics kernel for this stats-only pass.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import Mesh

from repro.core.kmeans import KMeansConfig
from repro.core.parallel import ParallelContext

Array = jax.Array


def make_distributed_kmeans(mesh: Mesh, cfg: KMeansConfig,
                            data_axes: Sequence[str] = ("data",),
                            k_axis: str | None = None,
                            compress_pod_axis: str | None = None):
    """Build ``fit(x_sharded, c0) -> (centroids, assignments, inertia)``.

    ``x`` must be sharded P((*data_axes,), None); ``c0`` replicated (or
    sharded P(k_axis, None) when ``k_axis`` is given). The Lloyd loop
    runs entirely inside one shard_map'd program: one collective round
    per iteration. See ``ParallelContext.make_kmeans_fit``.
    """
    pctx = ParallelContext(mesh, data_axes=data_axes, k_axis=k_axis)
    loop = pctx.make_kmeans_fit(cfg, compress_pod_axis=compress_pod_axis)

    def fit(x, c0):
        return loop(x, c0)[:3]
    return fit


def shard_points(mesh: Mesh, x, data_axes: Sequence[str] = ("data",)):
    """Place a host array onto the mesh, sharded along N."""
    return ParallelContext(mesh, data_axes=data_axes).shard_points(x)
