"""Continuous-batching SearchEngine scheduler acceptance tests.

The serving contract: ``submit``/``submit_add`` admit requests of any
row count into one FIFO queue; ``pump`` drains it — consecutive search
requests coalesce into padded power-of-two units, oversized requests
split (the tail keeps its place in line), adds apply between in-flight
units — and every result is bitwise what the same operations produce
synchronously in FIFO order. No fixed-shape rejection, ever.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index import IVFIndex
from repro.serve.engine import SearchConfig, SearchEngine

K, D = 16, 16


def _blobs(seed, n, spread=6.0, noise=0.3):
    key = jax.random.PRNGKey(seed)
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (K, D)) * spread
    assign = jax.random.randint(ka, (n,), 0, K)
    return np.asarray(centers[assign]
                      + jax.random.normal(kn, (n, D)) * noise)


@pytest.fixture(scope="module")
def corpus():
    return _blobs(0, 1024), _blobs(7, 300)


def _engine(x, **kw):
    scfg = SearchConfig(topk=5, nprobe=4, query_batch=32,
                        refresh_every=2, **kw)
    return SearchEngine(IVFIndex.build(x, k=K, max_iters=6, seed=0), scfg)


def test_interleaved_queue_matches_synchronous_fifo(corpus):
    """submit/submit_add traffic drained through the queue produces
    bitwise the results of the same operations run synchronously in
    admission order — adds land between units, never reordered."""
    x, q = corpus
    eng = _engine(x)
    ref = _engine(x)
    ops = [("search", q[:20]), ("add", q[20:84]),
           ("search", q[84:100]), ("add", q[100:164]),
           ("search", q[164:230]), ("search", q[230:260])]
    rids = [(kind, eng.submit(p) if kind == "search"
             else eng.submit_add(p)) for kind, p in ops]
    assert eng.queue_depth == len(ops)
    got = [(kind, eng.take(rid)) for kind, rid in rids]
    assert eng.queue_depth == 0
    for (kind, payload), (_, res) in zip(ops, got):
        if kind == "search":
            ids_ref, d_ref = ref.search(payload)
            np.testing.assert_array_equal(np.asarray(res[0]),
                                          np.asarray(ids_ref))
            np.testing.assert_array_equal(np.asarray(res[1]),
                                          np.asarray(d_ref))
        else:
            np.testing.assert_array_equal(np.asarray(res),
                                          np.asarray(ref.add(payload)))
    assert eng.interleaved_adds == 2
    assert eng.refresh_count == ref.refresh_count == 1


def test_consecutive_searches_coalesce_into_units(corpus):
    """Eight 4-row requests = one 32-row unit: one padded dispatch, all
    eight results scattered back bitwise."""
    x, q = corpus
    eng = _engine(x)
    rids = [eng.submit(q[4 * i:4 * i + 4]) for i in range(8)]
    eng.pump()
    assert eng.batches_formed == 1
    assert eng.coalesced_requests == 8
    ids_ref, _ = _engine(x).search(q[:32])
    for i, rid in enumerate(rids):
        ids, dists = eng.take(rid)
        assert ids.shape == (4, 5) and dists.shape == (4, 5)
        np.testing.assert_array_equal(np.asarray(ids),
                                      np.asarray(ids_ref[4 * i:4 * i + 4]))


def test_ragged_sizes_never_rejected(corpus):
    """Any row count — 0, 1, sub-bucket, bucket-straddling, larger than
    query_batch — is served, shape-correct and bitwise stable."""
    x, q = corpus
    eng = _engine(x)
    ref = _engine(x)
    for n in (0, 1, 7, 9, 31, 33, 100):
        ids, dists = eng.search(q[:n])
        assert ids.shape == (n, 5) and dists.shape == (n, 5)
        ids_ref, d_ref = ref.search(q[:n])
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
        np.testing.assert_array_equal(np.asarray(dists), np.asarray(d_ref))
    assert eng.queue_depth == 0


def test_oversized_request_splits_and_reassembles(corpus):
    """A 100-row request over a 32-row unit budget runs as ceil(100/32)
    units; the tail keeps its place at the head of the line and the
    slices concatenate back into one (100, topk) result."""
    x, q = corpus
    eng = _engine(x)
    rid = eng.submit(q[:100])
    eng.pump()
    assert eng.batches_formed == 4
    ids, dists = eng.take(rid)
    assert ids.shape == (100, 5)
    assert eng.queries_served == 100
    # self-queries (q is drawn off-corpus here, so compare vs direct)
    ids_ref, _ = eng.index.search(jnp.asarray(q[:100]), topk=5, nprobe=4)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))


def test_adds_interleave_between_search_units(corpus):
    """search | add | search admitted together: the first unit runs on
    the pre-add index, the second sees the inserted rows."""
    x, q = corpus
    eng = _engine(x)
    n0 = len(eng.index)
    new = np.asarray(eng.index.centroids[:8]) + 0.02
    r1 = eng.submit(q[:8])
    ra = eng.submit_add(new)
    r2 = eng.submit(new)               # should hit the new rows exactly
    eng.pump()
    assert eng.interleaved_adds == 1
    ids1, _ = eng.take(r1)
    assert int(np.asarray(ids1).max()) < n0
    cells = eng.take(ra)
    assert cells.shape == (8,)
    ids2, d2 = eng.take(r2)
    np.testing.assert_array_equal(np.asarray(ids2[:, 0]),
                                  n0 + np.arange(8))
    np.testing.assert_allclose(np.asarray(d2[:, 0]), 0.0, atol=1e-3)


def test_admission_backpressure(corpus):
    x, q = corpus
    eng = _engine(x, queue_max=3)
    for i in range(3):
        eng.submit(q[i:i + 1])
    with pytest.raises(RuntimeError, match="admission queue full"):
        eng.submit(q[:1])
    with pytest.raises(RuntimeError, match="admission queue full"):
        eng.submit_add(q[:1])
    eng.pump()                         # drains: admission reopens
    assert eng.queue_depth == 0
    eng.submit(q[:1])


def test_take_unknown_rid_raises(corpus):
    x, q = corpus
    eng = _engine(x)
    with pytest.raises(KeyError, match="unknown or lost"):
        eng.take(999)


# --- overlapped dispatch pipeline ------------------------------------------

def test_pump_overlaps_units_and_stays_bitwise(corpus):
    """The async contract: with pipeline_depth=2, pump dispatches unit
    i+1 while unit i's arrays are still in flight (structural overlap,
    counted by ``overlap_hits``) — and the results remain bitwise the
    synchronous FIFO answers."""
    x, q = corpus
    eng = _engine(x)
    ref = _engine(x)
    rids = [eng.submit(q[32 * i:32 * i + 32]) for i in range(4)]
    eng.pump()
    assert eng.batches_formed == 4
    # every dispatch after the first found an earlier unit un-synced
    assert eng.overlap_hits == 3
    for i, rid in enumerate(rids):
        ids, dists = eng.take(rid)
        ids_ref, d_ref = ref.search(q[32 * i:32 * i + 32])
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
        np.testing.assert_array_equal(np.asarray(dists), np.asarray(d_ref))
    assert eng.latency_stats()["inflight"] == 0


def test_pipeline_depth_bounds_inflight_units(corpus):
    x, q = corpus
    eng = _engine(x, pipeline_depth=2)
    for i in range(5):
        eng.submit(q[32 * i:32 * i + 32])
    eng.pump()
    assert eng.latency_stats()["inflight"] <= 2


def test_take_settles_inflight_before_returning(corpus):
    """take() must not hand back a result whose unit is still in
    flight: after take(rid) no in-flight unit carries rid."""
    x, q = corpus
    eng = _engine(x)
    r1 = eng.submit(q[:32])
    r2 = eng.submit(q[32:64])
    eng.pump()
    ids, _ = eng.take(r1)
    assert not any(r1 in rids for rids, *_ in eng._inflight)
    assert ids.shape == (32, 5)
    eng.take(r2)
    assert eng.latency_stats()["inflight"] == 0


def test_latency_stats_honest_timing(corpus, tracing):
    """dispatch/complete percentiles are read from the engine's
    ``engine.dispatch`` and ``engine.complete`` spans and sample only warm
    shape buckets (the first unit of a bucket pays compile and is left
    out), and completion latency can never be below dispatch latency."""
    x, q = corpus
    eng = _engine(x)
    lat0 = eng.latency_stats()
    assert lat0["dispatch_p50_ms"] == 0.0 and lat0["overlap_hits"] == 0
    for _ in range(3):                  # unit 1 compiles, 2-3 sample
        eng.search(q[:32])
    mine = {}
    for name, t0, t1, _parent, attrs in tracing.snapshot()["spans"]:
        if attrs.get("engine") == eng._eid:
            mine.setdefault(name, {})[attrs["unit"]] = (t0, t1)
    assert sorted(mine["engine.dispatch"]) == [1, 2, 3]
    assert sorted(mine["engine.complete"]) == [1, 2, 3]
    warm_dispatch = [(t1 - t0) / 1e6 for u, (t0, t1)
                     in mine["engine.dispatch"].items() if u > 1]
    warm_complete = [(mine["engine.complete"][u][1] - t0) / 1e6
                     for u, (t0, _t1) in mine["engine.dispatch"].items()
                     if u > 1]
    lat = eng.latency_stats()
    assert lat["dispatch_p50_ms"] == pytest.approx(
        np.percentile(warm_dispatch, 50))
    assert lat["complete_p99_ms"] == pytest.approx(
        np.percentile(warm_complete, 99))
    assert lat["dispatch_p50_ms"] > 0.0
    assert lat["complete_p50_ms"] >= lat["dispatch_p50_ms"]
    assert lat["complete_p99_ms"] >= lat["complete_p50_ms"]
    assert lat["inflight"] == 0


def test_latency_stats_after_obs_reset(corpus, tracing):
    """``obs.reset()`` between units drops the units recorded before it:
    the next unit of the bucket is then left out as if it paid compile,
    and the units after it are sampled again."""
    x, q = corpus
    eng = _engine(x)
    for _ in range(3):
        eng.search(q[:32])
    tracing.reset()
    assert eng.latency_stats()["dispatch_p50_ms"] == 0.0
    eng.search(q[:32])                  # taken as the bucket's first unit
    assert eng.latency_stats()["complete_p50_ms"] == 0.0
    eng.search(q[:32])
    lat = eng.latency_stats()
    assert lat["dispatch_p50_ms"] > 0.0
    assert lat["complete_p50_ms"] >= lat["dispatch_p50_ms"]
