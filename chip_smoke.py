#!/usr/bin/env python3
"""On-chip smoke run of the two main paths: the k-means fit and FlashIVF
search serving, at real sizes, every Pallas kernel compiled for the TPU.

    python3 chip_smoke.py                # one chip: four phases (below)
    python3 chip_smoke.py --four-chips   # four chips: the sharded paths only

One-chip phases (data generated on the device from ``--seed``):

1. fit, fused regime: ``KMeans`` with K=1024 on N=8,388,608 x d=128
   points (the planner must pick the fused FlashLloyd step);
2. fit, two-pass regime: K=65,536 on N=1,048,576 x d=512 (FlashAssign +
   sort-inverse update);
3. search, default index: ``IVFIndex`` (K=1024) over N=1,048,576 x d=128
   clustered points, served by ``SearchEngine`` — 16 batches of 128
   queries at nprobe=32, topk=10 — with recall@10 checked against exact
   brute force over the corpus;
4. search, quantized path: the same corpus on the paged store with q8
   codes, the device rescore cache and the two-level router.

``--four-chips`` runs the paths that exist only across chips, each next
to its single-device twin: the cells-sharded index on a 1x4 mesh (ids
identical to one device's), the K-sharded fit (centroids close to one
device's) and the N-sharded step of ``KMeans(cfg, mesh)`` on a 4x1 mesh
(its first step checked as the K-sharded one is).

Every check raises on failure, so the exit code is non-zero. Without a
TPU the script fails before any phase and prints no result. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
# keep libtpu's logs and the kernel plan cache out of /tmp and $HOME
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("REPRO_PLAN_CACHE", "off")


# the paper's two fit regimes: fused FlashLloyd, two-pass
FUSED = dict(n=1 << 23, d=128, k=1024, iters=5)
TWO_PASS = dict(n=1 << 20, d=512, k=65536, iters=2)
# the search corpus (SIFT1M-shaped) and its serving load
SEARCH_N, SEARCH_D, SEARCH_K = 1 << 20, 128, 1024
N_BATCHES, BATCH, NPROBE, TOPK = 16, 128, 32, 10


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  check {'PASS' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


# --- compile-time accounting ----------------------------------------------

_COMPILE_S = [0.0]


def _on_event(event: str, duration: float, **_kw) -> None:
    if event in ("/jax/core/compile/backend_compile_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration"):
        _COMPILE_S[0] += duration


@contextlib.contextmanager
def phase_timer(name: str):
    """Wall time of a block, with the lowering + backend-compile seconds
    that JAX reported inside it split out."""
    import jax
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = _COMPILE_S[0] - c0
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"  time {name}: wall {wall:.3f}s = compile {comp:.3f}s + "
          f"run {wall - comp:.3f}s; device-0 peak_bytes_in_use {peak}",
          flush=True)


# --- data -------------------------------------------------------------------

def blobs(key, n: int, d: int, n_centers: int, chunk: int = 1 << 18):
    """``n`` points around ``n_centers`` Gaussian centers (scale 5, noise
    0.4 — ``launch/serve.py``'s corpus), generated on the device in
    chunks so no full-size temporary exists besides the output."""
    import jax
    import jax.numpy as jnp
    chunk = min(chunk, n)
    assert n % chunk == 0

    @jax.jit
    def gen(key):
        kc, kp = jax.random.split(key)
        centers = jax.random.normal(kc, (n_centers, d)) * 5.0

        def one(i):
            ka, kn = jax.random.split(jax.random.fold_in(kp, i))
            lbl = jax.random.randint(ka, (chunk,), 0, n_centers)
            return centers[lbl] + 0.4 * jax.random.normal(kn, (chunk, d))

        return jax.lax.map(one, jnp.arange(n // chunk)).reshape(n, d)

    x = gen(key)
    x.block_until_ready()
    return x


def _highest(fn):
    """Run a reference at full f32 matmul precision (XLA's default on a
    TPU is one bf16 pass, too coarse to judge near-ties)."""
    import jax

    def wrapped(*a):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*a)
    return wrapped


def show_plan(op: str, shape: tuple):
    """Print (and return) the planner's plan for one kernel dispatch."""
    from repro.core.plan import default_planner
    p = default_planner().plan(op, shape)
    print(f"  plan {op}{tuple(shape)}: impl={p.impl} tiles={tuple(p.blocks)}"
          f" vmem_model={p.vmem_bytes}B budget={p.vmem_budget}B hw={p.hw}",
          flush=True)
    return p


# --- checks against kernels/ref.py ------------------------------------------

def _pair_dists(x, c, a, b):
    """Squared distances of every point to its centroid under two
    assignments (one fused pass on the device)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, c, a, b):
        return (jnp.sum((x - c[a]) ** 2, axis=-1),
                jnp.sum((x - c[b]) ** 2, axis=-1))
    return f(x, c, a, b)


def check_step_vs_reference(km, x, c, label: str) -> None:
    """One Lloyd step through ``KMeans.iterate`` vs the dense reference:
    assignments may differ only on near-ties (8 f32 ulps of the distance
    scale); the centroids equal the reference update of the kernel's own
    assignments (so a near-tie swap cannot move them); the inertia is
    the reference's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    k = c.shape[0]
    c1, a1, j1 = km.iterate(x, c)
    a_r, m_r = _highest(ref.assign_ref)(x, c)
    j_r = jnp.sum(m_r)
    s_r, cnt_r = jax.jit(ref.update_scatter_ref, static_argnums=2)(x, a1, k)
    c_r = ops.finalize_centroids(s_r, cnt_r, c)

    da, db = (np.asarray(v) for v in _pair_dists(x, c, a1, a_r))
    diff = np.asarray(a1) != np.asarray(a_r)
    tol = 8 * np.finfo(np.float32).eps * float(np.max(db) + 1.0)
    bad = int(np.sum(diff & (np.abs(da - db) > tol)))
    print(f"  {label}: {int(diff.sum())} of {x.shape[0]} assignments differ "
          f"from the dense reference, {bad} beyond the near-tie tolerance "
          f"{tol:.3g}", flush=True)
    check(bad == 0, f"{label}: assignments match the dense reference "
                    "up to near-ties")
    err = float(jnp.max(jnp.abs(c1.astype(jnp.float32) - c_r)))
    print(f"  {label}: max |centroid - reference| = {err:.3g}", flush=True)
    check(np.allclose(np.asarray(c1), np.asarray(c_r), rtol=1e-5, atol=1e-4),
          f"{label}: centroids allclose to the reference update "
          "(rtol 1e-5, atol 1e-4)")
    check(abs(float(j1) - float(j_r)) <= 1e-4 * abs(float(j_r)),
          f"{label}: inertia {float(j1):.6g} within 1e-4 of the "
          f"reference {float(j_r):.6g}")


def fit_phase(name: str, *, n: int, d: int, k: int, iters: int,
              n_check: int, want_impl: str, seed: int,
              check_update: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import KMeans, KMeansConfig
    from repro.core.init import init_centroids
    from repro.kernels import ops, ref

    print(f"phase {name}: KMeans k={k} max_iters={iters} on N={n} x d={d} "
          "f32", flush=True)
    key = jax.random.PRNGKey(seed)
    with phase_timer(f"{name} data"):
        x = blobs(jax.random.fold_in(key, 1), n, d, k)
    impl = show_plan("step", (n, k, d)).impl
    check(impl == want_impl, f"planner picks step impl {want_impl!r} "
                             f"(got {impl!r})")
    cfg = KMeansConfig(k=k, max_iters=iters, tol=0.0)
    km = KMeans(cfg)
    fkey = jax.random.fold_in(key, 2)
    with phase_timer(f"{name} fit ({iters} Lloyd iterations)"):
        st = km.fit(fkey, x)
        jax.block_until_ready(st)
    check(int(st.iteration) == iters, f"fit ran {iters} iterations "
                                      f"(got {int(st.iteration)})")
    check(bool(jnp.all(jnp.isfinite(st.centroids))), "centroids finite")

    # the same iterations one public step at a time, for the inertia curve
    c = init_centroids(fkey, x, k, cfg.init)
    inertia = []
    with phase_timer(f"{name} {iters} x KMeans.iterate"):
        for _ in range(iters):
            c, _, j = km.iterate(x, c)
            inertia.append(float(j))
    print(f"  inertia per iteration: {inertia}", flush=True)
    check(all(b <= a * (1 + 1e-5) for a, b in zip(inertia, inertia[1:])),
          "inertia does not rise from one iteration to the next "
          "(1e-5 relative f32 slack)")
    check(abs(inertia[-1] - float(st.inertia)) <= 1e-5 * inertia[-1],
          f"step-by-step inertia {inertia[-1]:.7g} matches the fit's "
          f"{float(st.inertia):.7g} (1e-5 relative)")

    xs = x[:n_check]
    show_plan("step", (n_check, k, d))
    with phase_timer(f"{name} reference check on {n_check} points"):
        check_step_vs_reference(km, xs, st.centroids,
                                f"{name} step on first {n_check} points")
        if check_update:
            a_r, _ = _highest(ref.assign_ref)(xs, st.centroids)
            show_plan("update", (n_check, k, d))
            s, cnt = ops.sort_inverse_update(xs, a_r, k=k)
            s_r, cnt_r = jax.jit(ref.update_scatter_ref,
                                 static_argnums=2)(xs, a_r, k)
            check(bool(jnp.array_equal(cnt, cnt_r)),
                  "sort_inverse_update counts == update_scatter_ref")
            check(np.allclose(np.asarray(s), np.asarray(s_r), rtol=1e-5,
                              atol=1e-3),
                  "sort_inverse_update sums allclose to update_scatter_ref "
                  "(rtol 1e-5, atol 1e-3)")
    del x, xs, st


# --- search -----------------------------------------------------------------

def search_corpus(seed: int):
    import jax
    key = jax.random.PRNGKey(seed)
    x = blobs(jax.random.fold_in(key, 3), SEARCH_N, SEARCH_D, SEARCH_K)
    qi = jax.random.randint(jax.random.fold_in(key, 4),
                            (N_BATCHES * BATCH,), 0, SEARCH_N)
    return x, x[qi]


def serve(index, q, label: str):
    """16 batches of 128 through ``SearchEngine`` (no health policy: a
    failed search raises)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.engine import SearchConfig, SearchEngine
    eng = SearchEngine(index, SearchConfig(topk=TOPK, nprobe=NPROBE,
                                           query_batch=BATCH), health=None)
    names = (("probe_n", "probe_k", "scan_b", "scan_c")
             if index.router.kind == "flat" else
             ("coarse_n", "coarse_k", "route_b", "route_c", "scan_b",
              "scan_c"))
    if index.codec_kind != "fp32":
        names = names[:-2] + ("q8_b", "q8_w", "rescore_b", "rescore_c")
    tiles = dict(zip(names, eng.pinned_plan))
    print(f"  plan {label} search tiles (B={BATCH}): {tiles}", flush=True)
    ids, times = [], []
    c0 = _COMPILE_S[0]
    for i in range(N_BATCHES):
        t0 = time.perf_counter()
        got, dists = eng.search(q[i * BATCH:(i + 1) * BATCH])
        jax.block_until_ready((got, dists))
        times.append(time.perf_counter() - t0)
        ids.append(got)
        if i in (0, N_BATCHES - 1):
            check(bool(jnp.all(jnp.isfinite(dists))),
                  f"{label} batch {i} distances finite")
    steady = sorted(times[1:])
    print(f"  time {label} serve: first batch {times[0]:.3f}s (compile "
          f"{_COMPILE_S[0] - c0:.3f}s in the run); later batches p50 "
          f"{steady[len(steady) // 2] * 1e3:.3f}ms max "
          f"{steady[-1] * 1e3:.3f}ms", flush=True)
    return jnp.concatenate(ids, axis=0)


def check_recall(x, q, ids, label: str) -> None:
    """recall@10 against exact brute force over the corpus rows — the
    ``ref.probe_ref`` oracle ``IVFIndex.search_brute`` applies to the
    store's rows, applied to the generated corpus itself (the index
    numbers vectors by corpus row). Going to the corpus keeps the
    reference independent of the index, and off the quantized store's
    host-side dense view, which took 202 s for 16 batches on a v5e."""
    import numpy as np
    from repro.index import recall_at_k
    from repro.kernels import ref
    exact = _highest(lambda qb, xs: ref.probe_ref(qb, xs, TOPK)[0])
    refs = [np.asarray(exact(q[i * BATCH:(i + 1) * BATCH], x))
            for i in range(N_BATCHES)]
    rec = recall_at_k(ids, np.concatenate(refs, axis=0))
    print(f"  {label}: recall@{TOPK} = {rec:.4f} over "
          f"{N_BATCHES * BATCH} queries", flush=True)
    check(rec >= 0.9, f"{label}: recall@{TOPK} >= 0.9")


def search_phase(name: str, seed: int, **index_kw) -> None:
    import jax
    from repro.index import IVFIndex
    print(f"phase {name}: IVFIndex k={SEARCH_K} over N={SEARCH_N} x "
          f"d={SEARCH_D} ({index_kw or 'default store, codec, router'}); "
          f"{N_BATCHES} batches of {BATCH}, nprobe={NPROBE}, topk={TOPK}",
          flush=True)
    with phase_timer(f"{name} data"):
        x, q = search_corpus(seed)
    show_plan("step", (SEARCH_N, SEARCH_K, SEARCH_D))
    with phase_timer(f"{name} build"):
        index = IVFIndex.build(x, k=SEARCH_K, **index_kw)
        index.block_until_ready()
    print(f"  index: {index!r}; {index.resident_bytes()} resident bytes",
          flush=True)
    with phase_timer(f"{name} serve"):
        ids = serve(index, q, name)
    del index
    with phase_timer(f"{name} brute-force reference"):
        check_recall(x, q, ids, name)
    del x


# --- four chips -------------------------------------------------------------

def bytes_per_device(label: str, index=None) -> list[int]:
    """Print each device's ``bytes_in_use``; with ``index``, also return
    the bytes of the index's posting-list arrays held on each device."""
    import jax
    devs = jax.devices()
    used = [(dv.memory_stats() or {}).get("bytes_in_use") for dv in devs]
    print(f"  {label}: bytes_in_use per device {used}", flush=True)
    if index is None:
        return used
    held = {dv: 0 for dv in devs}
    for arr in index.store.device_arrays():
        for shard in arr.addressable_shards:
            held[shard.device] += shard.data.nbytes
    held = [held[dv] for dv in devs]
    print(f"  {label}: posting-list bytes per device {held}", flush=True)
    return held


def check_first_step(label: str, x, c0, a1, c1, a1_ref, c1_ref) -> None:
    """A sharded first Lloyd step ``(c1, a1)`` against one device's
    ``(c1_ref, a1_ref)`` from the same centroids ``c0``: assignments equal
    up to near-ties, centroids the reference update of the step's own
    assignments, and one device's where no near-tie swap touched them."""
    import jax
    import numpy as np
    from repro.kernels import ops, ref
    n, k = x.shape[0], c0.shape[0]
    a1 = jax.device_put(a1, x.sharding)
    da, db = (np.asarray(v) for v in _pair_dists(x, c0, a1, a1_ref))
    diff = np.asarray(a1) != np.asarray(a1_ref)
    tol = 8 * np.finfo(np.float32).eps * float(np.max(db) + 1.0)
    print(f"  first step: {int(diff.sum())} of {n} assignments differ from "
          "one device's", flush=True)
    check(not np.any(diff & (np.abs(da - db) > tol)),
          f"{label} first-step assignments match one device's up to "
          f"near-ties ({tol:.3g})")
    s_r, cnt_r = jax.jit(ref.update_scatter_ref, static_argnums=2)(x, a1, k)
    c1_r = ops.finalize_centroids(s_r, cnt_r, c0)
    c1, c1_ref = np.asarray(c1), np.asarray(c1_ref)
    check(np.allclose(c1, np.asarray(c1_r), rtol=1e-5, atol=1e-4),
          f"{label} first-step centroids allclose to the reference update "
          "of its assignments (rtol 1e-5, atol 1e-4)")
    moved = np.zeros(k, bool)     # centroids a near-tie swap touched
    moved[np.asarray(a1)[diff]] = moved[np.asarray(a1_ref)[diff]] = True
    print(f"  first step: max |{label} - one device| = "
          f"{np.max(np.abs(c1 - c1_ref)):.3g}", flush=True)
    check(np.allclose(c1[~moved], c1_ref[~moved], rtol=1e-5, atol=1e-4),
          f"{label} first-step centroids allclose to one device's "
          f"(rtol 1e-5, atol 1e-4; {int(moved.sum())} touched by near-tie "
          "swaps excluded)")


def four_chip_phases(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import KMeans, KMeansConfig
    from repro.core.init import init_centroids
    from repro.core.parallel import ParallelContext, parse_mesh_flag
    from repro.index import IVFIndex

    check(len(jax.devices()) == 4, f"four devices (got {len(jax.devices())})")

    print(f"phase sharded_search: IVFIndex k={SEARCH_K} on a 1x4 mesh vs "
          "one device", flush=True)
    x, q = search_corpus(seed)
    pctx = ParallelContext.for_mesh(parse_mesh_flag("1x4"))
    print(f"  {pctx.describe()}", flush=True)
    with phase_timer("sharded_search single-device build"):
        one = IVFIndex.build(x, k=SEARCH_K)
        one.block_until_ready()
    with phase_timer("sharded_search single-device serve"):
        ids_one = serve(one, q, "single-device")
    del one
    with phase_timer("sharded_search 1x4 build"):
        sh = IVFIndex.build(x, k=SEARCH_K, pctx=pctx)
        sh.block_until_ready()
    print(f"  index: {sh!r}", flush=True)
    del x
    held = bytes_per_device("after the sharded build", sh)
    with phase_timer("sharded_search 1x4 serve"):
        ids_sh = serve(sh, q, "1x4")
    same = np.asarray(ids_sh) == np.asarray(ids_one)
    print(f"  {int(same.sum())} of {same.size} result ids identical",
          flush=True)
    check(bool(same.all()), "sharded ids identical to the single-device "
                            f"index at nprobe={NPROBE}")
    check(all(h > 0 for h in held), "posting lists on all four devices")
    del sh

    n, d, k, iters = (FUSED[f] for f in ("n", "d", "k", "iters"))
    print(f"phase k_sharded_fit: K={k} over a 1x4 model axis vs one device, "
          f"N={n} x d={d}, {iters} iterations", flush=True)
    key = jax.random.PRNGKey(seed)
    with phase_timer("k_sharded_fit data"):
        x = blobs(jax.random.fold_in(key, 1), n, d, k)
    cfg = KMeansConfig(k=k, max_iters=iters, tol=0.0)
    km = KMeans(cfg)
    fkey = jax.random.fold_in(key, 2)
    c0 = init_centroids(fkey, x, k, cfg.init)
    with phase_timer("k_sharded_fit single-device fit and first step"):
        st = km.fit(fkey, x)
        c1_ref, a1_ref, _ = km.iterate(x, c0)
        jax.block_until_ready((st, c1_ref))
    kp = ParallelContext(parse_mesh_flag("1x4"), data_axes=("data",),
                         k_axis="model")
    print(f"  {kp.describe()}", flush=True)
    with phase_timer("k_sharded_fit 1x4 fit and first step"):
        xs, c0s = kp.shard_points(x), kp.shard_centroids(c0)
        st_k = kp.make_kmeans_fit(cfg)(xs, c0s)
        c, j = st_k.centroids, st_k.inertia
        first = kp.make_kmeans_fit(
            KMeansConfig(k=k, max_iters=1, tol=0.0))(xs, c0s)
        c1, a1 = first.centroids, first.assignments
        jax.block_until_ready((c, c1))
    bytes_per_device("during the K-sharded fit")
    del xs

    # one Lloyd step from the same centroids: the two-stage argmin and
    # the owned statistics must reproduce one device's step
    check_first_step("K-sharded", x, c0, a1, c1, a1_ref, c1_ref)

    # the whole fit: near-tie swaps compound over the iterations, so the
    # centroids are reported and the inertia is what must agree
    err = np.abs(np.asarray(c) - np.asarray(st.centroids))
    print(f"  {iters}-iteration fit: max |K-sharded - one device| = "
          f"{err.max():.3g}; {int(np.sum(err.max(axis=1) > 1e-4))} of {k} "
          "centroids differ by more than 1e-4", flush=True)
    rel = abs(float(j) - float(st.inertia)) / float(st.inertia)
    check(rel <= 1e-4, f"{iters}-iteration inertia {float(j):.7g} within "
                       f"1e-4 of one device's {float(st.inertia):.7g}")

    print(f"phase n_sharded_fit: N={n} over a 4x1 data axis vs one device, "
          f"K={k}, d={d}: KMeans(cfg, mesh).iterate", flush=True)
    km4 = KMeans(KMeansConfig(k=k, max_iters=iters, tol=0.0),
                 mesh=parse_mesh_flag("4"))
    print(f"  {km4.pctx.describe()}", flush=True)
    with phase_timer("n_sharded_fit 4x1 first step"):
        xs = km4.pctx.shard_points(x)
        c1n, a1n, _ = km4.iterate(xs, c0)
        jax.block_until_ready((c1n, a1n))
    bytes_per_device("during the N-sharded step")
    del xs
    check_first_step("N-sharded", x, c0, a1n, c1n, a1_ref, c1_ref)


# --- main -------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded search, the K-sharded fit "
                         "and the N-sharded step against their "
                         "single-device twins (4 chips)")
    args = ap.parse_args()

    try:
        from repro.kernels import ops
        from repro.utils.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if ops.default_interpret():
        print("chip_smoke: kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    cache = configure_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    if args.four_chips:
        four_chip_phases(args.seed)
    else:
        fit_phase("fit_fused", **FUSED, n_check=1 << 18, want_impl="fused",
                  seed=args.seed)
        fit_phase("fit_two_pass", **TWO_PASS, n_check=8192,
                  want_impl="two_pass", seed=args.seed, check_update=True)
        search_phase("search_default", args.seed)
        search_phase("search_q8", args.seed, store="paged", codec="q8",
                     rescore="device", router="two_level")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
