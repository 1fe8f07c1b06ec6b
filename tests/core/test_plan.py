"""KernelPlanner: cache accounting, disk persistence, hardware detection,
measured-refinement folding, and the ops-wrapper VMEM audit."""
import json
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core import autotune
from repro.core import heuristics as H
from repro.core import plan as P
from repro.kernels import ops
from repro.kernels.ops import BlockConfig


def fresh(**kw):
    """Memory-only planner pinned to the v5e table (hermetic: no disk,
    no hardware detection)."""
    kw.setdefault("hw", H.TPU_V5E)
    kw.setdefault("persist", False)
    return P.KernelPlanner(**kw)


# ---------------------------------------------------------------------------
# cache accounting
# ---------------------------------------------------------------------------

def test_cache_hit_miss_accounting():
    pl = fresh()
    p1 = pl.plan("step", (100_000, 1024, 128))
    assert pl.counters()["misses"] == 1
    assert pl.counters()["chooser_calls"] == 1
    assert pl.counters()["hits"] == 0

    p2 = pl.plan("step", (100_000, 1024, 128))
    assert p2 == p1
    assert pl.counters() == {**pl.counters(), "hits": 1, "misses": 1}

    # same power-of-two bucket (100_000 -> 131072): still a pure hit
    p3 = pl.plan("step", (130_000, 1024, 128))
    assert p3 == p1
    assert pl.counters()["chooser_calls"] == 1

    # a different bucket is an honest miss
    pl.plan("step", (1_000_000, 1024, 128))
    assert pl.counters()["misses"] == 2
    assert pl.counters()["chooser_calls"] == 2


def test_step_plan_populates_assign_and_update_siblings():
    """assign/update of the same geometry share the step plan's
    choose_blocks run — asking for them must not re-plan."""
    pl = fresh()
    step = pl.plan("step", (65536, 512, 64))
    a = pl.plan("assign", (65536, 512, 64))
    u = pl.plan("update", (65536, 512, 64))
    assert pl.counters()["chooser_calls"] == 1
    assert a.blocks == (step.block.assign_block_n, step.block.assign_block_k)
    assert u.blocks == (step.block.update_block_n, step.block.update_block_k)


def test_plan_matches_heuristics_and_respects_budget():
    pl = fresh()
    for op, shape in [("assign", (65536, 1024, 128)),
                      ("update", (65536, 1024, 128)),
                      ("probe", (4096, 1024, 128, 16)),
                      ("scan", (256, 8192, 128, 10))]:
        p = pl.plan(op, shape)
        assert p.vmem_bytes <= H.TPU_V5E.vmem_bytes
        assert p.hbm_bytes > 0
        assert all(v >= 8 for v in p.blocks)
    # the step plan's impl agrees with the closed-form crossover rule
    for n, k, d in [(1_000_000, 1024, 128), (1_000_000, 65536, 512)]:
        assert pl.plan("step", (n, k, d)).impl == H.choose_step_impl(n, k, d)


def test_blk_pinned_plan_does_not_poison_base_entry():
    pl = fresh()
    base = pl.plan("step", (100_000, 1024, 128))
    forced = BlockConfig(fused_block_n=8, fused_block_k=8)
    pinned = pl.plan("step", (100_000, 1024, 128), blk=forced)
    assert pinned.block == forced
    assert pl.plan("step", (100_000, 1024, 128)) == base


def test_bad_op_and_shape_arity_raise():
    pl = fresh()
    with pytest.raises(ValueError, match="unknown plan op"):
        pl.plan("matmul", (8, 8, 8))
    with pytest.raises(ValueError, match="arity"):
        pl.plan("probe", (8, 8, 8))


# ---------------------------------------------------------------------------
# on-disk persistence
# ---------------------------------------------------------------------------

def test_disk_persistence_round_trip(tmp_path):
    path = tmp_path / "plans.json"
    a = fresh(cache_path=path)
    pa = a.plan("step", (65536, 512, 64))
    a.plan("probe", (1024, 512, 64, 8))
    assert path.exists()

    b = fresh(cache_path=path)
    pb = b.plan("step", (65536, 512, 64))
    b.plan("probe", (1024, 512, 64, 8))
    assert pb == pa
    assert b.counters()["chooser_calls"] == 0          # launch skipped planning
    assert b.counters()["disk_entries_loaded"] >= 2


def test_corrupt_cache_file_ignored(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text("{not json at all")
    pl = fresh(cache_path=path)
    p = pl.plan("step", (65536, 512, 64))              # must not raise
    assert pl.counters()["chooser_calls"] == 1
    # and the corrupt file is replaced by a valid one
    assert json.loads(path.read_text())["version"] == P.CACHE_VERSION
    assert fresh(cache_path=path).plan("step", (65536, 512, 64)) == p


def test_stale_version_cache_ignored(tmp_path):
    path = tmp_path / "plans.json"
    a = fresh(cache_path=path)
    a.plan("step", (65536, 512, 64))
    raw = json.loads(path.read_text())
    raw["version"] = P.CACHE_VERSION - 1
    path.write_text(json.dumps(raw))
    b = fresh(cache_path=path)
    b.plan("step", (65536, 512, 64))
    assert b.counters()["disk_entries_loaded"] == 0    # stale: ignored
    assert b.counters()["chooser_calls"] == 1          # re-planned, not fatal


def test_bad_disk_entry_skipped_not_fatal(tmp_path):
    path = tmp_path / "plans.json"
    a = fresh(cache_path=path)
    a.plan("step", (65536, 512, 64))
    raw = json.loads(path.read_text())
    key = next(iter(raw["plans"]))
    raw["plans"][key] = {"garbage": True}
    path.write_text(json.dumps(raw))
    b = fresh(cache_path=path)
    b.plan("step", (65536, 512, 64))                   # must not raise


# ---------------------------------------------------------------------------
# hardware detection
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


def test_detect_hardware_mapping_and_fallback():
    assert P.detect_hardware([_Dev("TPU v5 lite")]) is H.TPU_V5E
    assert P.detect_hardware([_Dev("TPU v5e")]) is H.TPU_V5E
    assert P.detect_hardware([_Dev("TPU v5p")]) is H.TPU_V5P
    assert P.detect_hardware([_Dev("TPU v5")]) is H.TPU_V5P
    assert P.detect_hardware([_Dev("TPU v4")]) is H.TPU_V4
    assert P.detect_hardware([_Dev("TPU v6e")]) is H.TPU_V6E
    # only the CPU backend (interpret mode) plans against the v5e row
    assert P.detect_hardware([_Dev("cpu", "cpu")]) is H.TPU_V5E
    assert P.detect_hardware() is H.TPU_V5E            # tests force the CPU
    # an unknown chip, another platform or no device at all is an error
    with pytest.raises(RuntimeError, match="no HARDWARE_TABLE row"):
        P.detect_hardware([_Dev("Tesla V100", "gpu")])
    with pytest.raises(RuntimeError, match="no devices"):
        P.detect_hardware([])


def test_detect_hardware_unknown_tpu_raises():
    with pytest.raises(RuntimeError, match="TPU v9"):
        P.detect_hardware([_Dev("TPU v9")])


def test_detect_hardware_enumeration_failure_raises(monkeypatch):
    def broken():
        raise RuntimeError("backend init failed")
    monkeypatch.setattr(P.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend init failed"):
        P.detect_hardware()


def test_planner_keys_are_hardware_specific(tmp_path):
    path = tmp_path / "plans.json"
    a = fresh(cache_path=path, hw=H.TPU_V5E)
    a.plan("step", (65536, 512, 64))
    b = fresh(cache_path=path, hw=H.TPU_V5P)
    b.plan("step", (65536, 512, 64))
    assert b.counters()["disk_entries_loaded"] == 0    # other chip's plans
    assert b.counters()["chooser_calls"] == 1


def test_disk_cache_serves_mixed_fleet_without_truncation(tmp_path):
    """One cache file, many chips: a planner for hardware B must merge
    into (never erase) hardware A's persisted plans — including when a
    write happens before this planner ever read the file."""
    path = tmp_path / "plans.json"
    a = fresh(cache_path=path, hw=H.TPU_V5E)
    a.plan("step", (65536, 512, 64))
    b = fresh(cache_path=path, hw=H.TPU_V5P)
    # fold_measured as the *first* operation: a store-before-load
    b.fold_measured(4096, 128, 32, report=_fake_report())
    c = fresh(cache_path=path, hw=H.TPU_V5E)
    c.plan("step", (65536, 512, 64))
    assert c.counters()["chooser_calls"] == 0          # v5e plans survived
    d = fresh(cache_path=path, hw=H.TPU_V5P)
    assert d.plan("step", (4096, 128, 32)).source == "measured"


def test_audit_uses_the_plans_hardware():
    """Tiles sized for a bigger-VMEM chip must be audited against that
    chip, not the default planner's detected hardware."""
    from repro.kernels.ops import _audit_blocks
    big = H.TPU_V6E.vmem_bytes                         # 2x v5e
    # pick (bn, d) so the footprint fits v6e but overflows v5e
    bn, d = 512, 2048                                  # ~19 MB working set
    assert H.TPU_V5E.vmem_bytes < H.assign_footprint(bn, 128, d, 4) <= big
    with warnings.catch_warnings():
        warnings.simplefilter("error")                 # any warn -> failure
        out = _audit_blocks("assign", bn, 128, d, 4, hw_name="tpu_v6e")
    assert out == (bn, 128)
    with pytest.warns(UserWarning, match="VMEM footprint"):
        shrunk = _audit_blocks("assign", bn, 128, d, 4, hw_name="tpu_v5e")
    assert shrunk != (bn, 128)


# ---------------------------------------------------------------------------
# measured refinement (the autotuner as a planner backend)
# ---------------------------------------------------------------------------

def _fake_report():
    return autotune.TuneReport(
        best=BlockConfig(assign_block_n=128, assign_block_k=128,
                         update_block_n=128, update_block_k=128),
        num_compiles=16, tune_seconds=0.1,
        best_assign_us=1.0, best_update_us=1.0, table={})


def test_fold_measured_updates_all_legs(tmp_path):
    path = tmp_path / "plans.json"
    pl = fresh(cache_path=path)
    pl.plan("step", (65536, 512, 64))
    step = pl.fold_measured(65536, 512, 64, report=_fake_report())
    assert step.source == "measured"
    assert (step.block.assign_block_n, step.block.assign_block_k) == (128, 128)
    for op in ("assign", "update", "step"):
        got = pl.plan(op, (65536, 512, 64))
        assert got.source == "measured"
    assert pl.plan("assign", (65536, 512, 64)).blocks == (128, 128)
    # measured plans persist across launches
    b = fresh(cache_path=path)
    assert b.plan("step", (65536, 512, 64)).source == "measured"
    assert b.counters()["chooser_calls"] == 0


def test_refine_measure_invokes_tuner_once(monkeypatch):
    calls = []

    def fake_tune(n, k, d, **kw):
        calls.append((n, k, d))
        return _fake_report()

    monkeypatch.setattr(autotune, "exhaustive_tune", fake_tune)
    pl = fresh()
    p1 = pl.plan("assign", (2048, 64, 32), refine="measure")
    assert p1.source == "measured" and p1.blocks == (128, 128)
    assert len(calls) == 1
    # already measured: served from cache, tuner not re-run
    p2 = pl.plan("assign", (2048, 64, 32), refine="measure")
    assert p2 == p1 and len(calls) == 1
    pl.plan("step", (2048, 64, 32), refine="measure")
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# wrapper integration: planner-backed defaults + VMEM audit
# ---------------------------------------------------------------------------

def test_ops_wrappers_plan_when_blocks_omitted():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (300, 16))
    c = jax.random.normal(jax.random.fold_in(key, 1), (24, 16))
    a, m = ops.flash_assign(x, c)                      # no magic defaults
    a_ref, m_ref = ops.flash_assign(x, c, block_n=64, block_k=32)
    assert (a == a_ref).all()
    s, cnt = ops.sort_inverse_update(x, a, k=24)
    s_ref, cnt_ref = ops.sort_inverse_update(x, a, k=24, block_n=64,
                                             block_k=32)
    assert jnp.allclose(s, s_ref) and jnp.allclose(cnt, cnt_ref)


def test_ops_wrapper_accepts_explicit_plan():
    pl = fresh()
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (256, 16))
    c = jax.random.normal(jax.random.fold_in(key, 1), (16, 16))
    p = pl.plan("assign", (256, 16, 16), x.dtype)
    a, _ = ops.flash_assign(x, c, plan=p)
    a_ref, _ = ops.flash_assign(x, c, block_n=p.blocks[0],
                                block_k=p.blocks[1])
    assert (a == a_ref).all()
    with pytest.raises(ValueError, match="cannot drive"):
        ops.flash_probe(x, c, l=4, plan=p)


def test_vmem_audit_autoshrinks_with_warning():
    key = jax.random.PRNGKey(2)
    # B_N * d * 4 = 1024 * 8192 * 4 = 32 MB resident tile >> 16 MB VMEM
    x = jax.random.normal(key, (1024, 8192))
    c = jax.random.normal(jax.random.fold_in(key, 1), (8, 8192))
    with pytest.warns(UserWarning, match="VMEM footprint"):
        a, _ = ops.flash_assign(x, c, block_n=1024, block_k=1024)
    a_ref, _ = ops.flash_assign(x, c, block_n=64, block_k=8)
    assert (a == a_ref).all()                          # shrunk, not wrong


def test_vmem_audit_raises_on_irreducible_working_set():
    from repro.kernels.ops import _audit_blocks
    # the fused accumulator K*d*4 alone dwarfs VMEM at minimal tiles
    with pytest.raises(ValueError, match="even at minimal"):
        _audit_blocks("fused", 8, 8, 1_000_000, 4, k=4096)


def test_kmeans_config_routes_through_planner():
    from repro.core.kmeans import KMeansConfig
    pl = fresh()
    cfg = KMeansConfig(k=64, planner=pl)
    b1 = cfg.blocks_for(4000, 128, 4)
    impl = cfg.resolved_step_impl(4000, 128, 4, blk=b1)
    assert pl.counters()["chooser_calls"] == 1         # one plan, reused
    b2 = cfg.blocks_for(4090, 128, 4)                  # same pow2 bucket
    assert b2 == b1
    assert pl.counters()["chooser_calls"] == 1
    assert impl in ("fused", "two_pass")
    # explicit cfg.block wins without consulting the planner
    cfg2 = KMeansConfig(k=64, block=b1, planner=pl)
    assert cfg2.blocks_for(64, 8, 4) is b1


def test_default_planner_swap():
    old = P.default_planner()
    try:
        mine = fresh()
        P.set_default_planner(mine)
        assert P.default_planner() is mine
    finally:
        P.set_default_planner(old)
