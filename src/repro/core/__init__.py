"""repro.core — flash-kmeans: IO-aware, contention-free exact k-means.

Public API:
  KMeans, KMeansConfig, KMeansState     — the composable module
  lloyd_step                            — single online iteration
  ParallelContext / build_mesh          — the one shard_map execution
                                          layer + the one mesh helper
  make_distributed_kmeans               — multi-chip/pod adapter over it
  ChunkedKMeans                         — out-of-core streaming driver
  StreamingKMeans / SufficientStats     — online/mini-batch driver + the
                                          shared reduction type
  KernelPlanner / KernelPlan            — the cache-aware planning layer
                                          every kernel dispatch goes through
  default_planner / detect_hardware     — process-wide planner + hw mapping
  choose_blocks / TPU_V5E               — closed-form heuristic internals
"""
from repro.core.chunked import ChunkedKMeans, ChunkedStats
from repro.core.distributed import make_distributed_kmeans, shard_points
from repro.core.heuristics import Hardware, TPU_V5E, choose_blocks
from repro.core.init import init_centroids, kmeans_plus_plus, random_init
from repro.core.kmeans import (KMeans, KMeansConfig, KMeansState, lloyd_stats,
                               lloyd_step, make_kmeans_fn)
from repro.core.parallel import (ParallelContext, build_mesh, make_host_mesh,
                                 make_production_mesh, parse_mesh_flag)
from repro.core.plan import (KernelPlan, KernelPlanner, default_planner,
                             detect_hardware, set_default_planner)
from repro.core.streaming import (StreamingKMeans, SufficientStats,
                                  partial_fit_step)

__all__ = [
    "KMeans", "KMeansConfig", "KMeansState", "lloyd_stats", "lloyd_step",
    "make_kmeans_fn",
    "make_distributed_kmeans", "shard_points", "ChunkedKMeans", "ChunkedStats",
    "ParallelContext", "build_mesh", "make_host_mesh", "make_production_mesh",
    "parse_mesh_flag",
    "StreamingKMeans", "SufficientStats", "partial_fit_step",
    "KernelPlan", "KernelPlanner", "default_planner", "detect_hardware",
    "set_default_planner",
    "choose_blocks", "Hardware", "TPU_V5E", "init_centroids",
    "kmeans_plus_plus", "random_init",
]
