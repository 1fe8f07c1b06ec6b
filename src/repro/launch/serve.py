"""Serving launcher: batched prefill+decode with optional clustered-KV,
plus a FlashIVF vector-search serving mode.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --batch 4 --prompt-len 128 --gen 32 --mode clustered

  PYTHONPATH=src python -m repro.launch.serve --mode search \
      --n 20000 --d 64 --kc 64 --queries 512 --topk 10 --nprobe 8

  # sharded serving: 1-way data x 8-way cells over 8 (fake) devices
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --mode search --mesh 1x8

  # reliability: durable snapshots + WAL, health ladder, seeded chaos
  PYTHONPATH=src python -m repro.launch.serve --mode search \
      --snapshot-dir /tmp/ivf-snap --health --chaos-seed 7
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import obs
from repro.configs.base import get_config
from repro.core.parallel import ParallelContext, parse_mesh_flag
from repro.models import model as M
from repro.serve.engine import Engine, SearchConfig, SearchEngine, ServeConfig
from repro.utils.compile_cache import configure_compile_cache


def _serve_lm(args) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params, _ = M.init_model(key, cfg,
                             max_pos=args.prompt_len + args.gen + 64)
    mesh = parse_mesh_flag(args.mesh) if args.mesh else None
    engine = Engine(cfg, params,
                    ServeConfig(max_seq=args.prompt_len + args.gen + 8,
                                mode=args.mode,
                                temperature=args.temperature),
                    mesh=mesh)

    tokens = jax.random.randint(jax.random.fold_in(key, 1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    frontend = None
    if cfg.frontend:
        frontend = jax.random.normal(
            jax.random.fold_in(key, 2),
            (args.batch, cfg.frontend_seq, cfg.d_model))

    t0 = time.time()
    out = engine.generate(tokens, args.gen, frontend=frontend, key=key)
    out.block_until_ready()
    dt = time.time() - t0
    print(f"arch={cfg.name} mode={args.mode} batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen}")
    print(f"wall {dt:.2f}s -> {args.batch*args.gen/dt:.1f} tok/s")
    print("sample ids:", out[0, :16].tolist())


def _serve_search(args) -> None:
    """Build a FlashIVF index over a synthetic clustered corpus and serve
    batched queries; reports build wall, QPS, and recall@topk vs brute.

    With ``--mesh DATAxCELLS`` the index is built and served through a
    ``ParallelContext``: build is data-parallel (O(K·d) psum per Lloyd
    iteration), cells + posting lists are partitioned over the cells
    axis, and every query batch runs the two-stage sharded search —
    the modeled cross-shard bytes per batch are reported alongside QPS.
    """
    from repro.index import IVFIndex, recall_at_k
    obs.enable()   # latency_stats reads the engine's spans

    from repro.reliability import FaultInjector, FaultPlan, HealthPolicy

    pctx = None
    if args.mesh:
        pctx = ParallelContext.for_mesh(parse_mesh_flag(args.mesh))
        print(f"sharded serving: {pctx.describe()}")

    key = jax.random.PRNGKey(args.seed)
    kc, ka, kn, kq = jax.random.split(key, 4)
    centers = jax.random.normal(kc, (args.kc, args.d)) * 5.0
    lbl = jax.random.randint(ka, (args.n,), 0, args.kc)
    x = centers[lbl] + 0.4 * jax.random.normal(kn, (args.n, args.d))

    t0 = time.time()
    rescore_mult = ("auto" if args.rescore_mult == "auto"
                    else int(args.rescore_mult))
    index = IVFIndex.build(x, k=args.kc, max_iters=args.kmeans_iters,
                           pctx=pctx, store=args.store,
                           page_size=args.page_size, codec=args.codec,
                           rescore_mult=rescore_mult,
                           rescore=args.rescore,
                           router=args.router)
    index.block_until_ready()
    t_build = time.time() - t0
    print(f"bucket store: {index.store!r} "
          f"({index.resident_bytes() / 1e6:.1f} MB resident)")
    if index.router.kind != "flat":
        print(f"router: {index.router!r}")

    scfg = SearchConfig(topk=args.topk, nprobe=args.nprobe,
                        query_batch=args.queries,
                        snapshot_dir=args.snapshot_dir,
                        snapshot_every=args.snapshot_every)
    health = HealthPolicy() if args.health else None
    faults = FaultInjector(FaultPlan.seeded(args.chaos_seed)) \
        if args.chaos_seed is not None else None
    eng = SearchEngine(index, scfg, health=health, faults=faults)
    q = x[jax.random.randint(kq, (args.queries,), 0, args.n)]
    ids, _ = eng.search(q)                     # compile + warm
    jax.block_until_ready(ids)
    t0 = time.time()
    for _ in range(args.reps):
        ids, dists = eng.search(q)
    jax.block_until_ready(ids)
    qps = args.reps * args.queries / (time.time() - t0)

    ids_ref, _ = index.search_brute(q, topk=args.topk)
    recall = recall_at_k(ids, ids_ref)
    print(f"mode=search n={args.n} d={args.d} kc={args.kc} "
          f"nprobe={args.nprobe} topk={args.topk}")
    print(f"build {t_build:.2f}s ({args.n / t_build:.0f} pts/s); "
          f"serve {qps:.0f} qps; recall@{args.topk}={recall:.3f}")
    print(f"scheduler: {eng.batches_formed} units, "
          f"{eng.coalesced_requests} coalesced, "
          f"{eng.interleaved_adds} interleaved adds, "
          f"queue depth {eng.queue_depth}")
    lat = eng.latency_stats()
    print(f"latency: dispatch p50 {lat['dispatch_p50_ms']:.3f}ms "
          f"p99 {lat['dispatch_p99_ms']:.3f}ms; "
          f"complete p50 {lat['complete_p50_ms']:.3f}ms "
          f"p99 {lat['complete_p99_ms']:.3f}ms; "
          f"{lat['overlap_hits']} overlapped units")
    if pctx is not None:
        cb = index.search_collective_bytes(args.queries, args.topk,
                                           args.nprobe)
        print(f"collective bytes/batch (modeled, O(b*L)): {cb}")
    if health is not None or faults is not None:
        hot = {k: v for k, v in eng.counters.as_dict().items() if v}
        print(f"health counters: {hot or 'all healthy'}")
    if args.snapshot_dir:
        # durability demo: snapshot, kill, recover, verify identity
        t0 = time.time()
        eng.snapshot()
        t_snap = time.time() - t0
        index.faults = None   # the dead engine's injector dies with it
        t0 = time.time()
        eng2 = SearchEngine.recover(args.snapshot_dir, scfg, pctx=pctx)
        t_rec = time.time() - t0
        ids2, _ = eng2.search(q)
        same = bool((jax.numpy.asarray(ids) == ids2).all())
        print(f"snapshot {t_snap*1e3:.1f}ms; recover {t_rec:.2f}s "
              f"(replayed {eng2.counters.wal_records_replayed} WAL "
              f"records); restored search identical: {same}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="dense",
                    choices=["dense", "clustered", "search"])
    ap.add_argument("--mesh", default=None,
                    help="serve on a DATAxCELLS host mesh (e.g. 1x8): "
                         "sharded FlashIVF for --mode search, model mesh "
                         "for dense/clustered (built via the one "
                         "core.parallel helper)")
    # LM serving
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    # vector-search serving
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--kc", type=int, default=64,
                    help="coarse cells (IVF k)")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--nprobe", type=int, default=8)
    ap.add_argument("--kmeans-iters", type=int, default=8)
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--store", default=None,
                    choices=["padded", "paged"],
                    help="posting-list backend (default: "
                         "REPRO_BUCKET_STORE env, else padded)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged-store page size in slots (default 64)")
    ap.add_argument("--codec", default=None,
                    choices=["fp32", "q8"],
                    help="posting-list payload codec (default: "
                         "REPRO_BUCKET_CODEC env, else fp32); q8 stores "
                         "int8 residual codes and searches in two phases "
                         "(quantized propose + exact fp32 rescore)")
    ap.add_argument("--rescore-mult", default="4",
                    help="two-phase proposal depth R = rescore_mult*topk "
                         "(q8 codec only); 'auto' defers to the "
                         "recall-target chooser")
    ap.add_argument("--rescore", default=None,
                    choices=["device", "host"],
                    help="q8 exact-rescore row source (default: "
                         "REPRO_RESCORE env, else device); device keeps "
                         "a set-associative id->fp32-row cache in device "
                         "memory fused into the search program (zero "
                         "host transfers per call); host round-trips "
                         "through the RescoreReservoir (parity oracle)")
    ap.add_argument("--router", default=None,
                    choices=["flat", "two_level"],
                    help="cell-selection router (default: REPRO_ROUTER "
                         "env, else flat); two_level probes a coarse "
                         "k-means over the K cells first — probe cost "
                         "O(K_c*d + nprobe_c*gcap*d) instead of O(K*d)")
    # reliability (--mode search)
    ap.add_argument("--snapshot-dir", default=None,
                    help="durable index snapshots + write-ahead add-log "
                         "here; also runs a kill/recover identity demo")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="adds between automatic snapshots (0 = manual)")
    ap.add_argument("--health", action="store_true",
                    help="serve under a HealthPolicy (retry/backoff + "
                         "degraded-mode ladder); prints health counters")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a seeded FaultPlan into the serving path "
                         "(deterministic chaos; implies interesting "
                         "counters)")
    args = ap.parse_args()
    configure_compile_cache()

    if args.mode == "search":
        _serve_search(args)
        return
    if not args.arch:
        ap.error("--arch is required for dense/clustered serving")
    _serve_lm(args)


if __name__ == "__main__":
    main()
