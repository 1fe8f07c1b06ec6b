"""Every Pallas kernel compiles for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, 1-D blocks whose layout
XLA and Mosaic disagree on, vector loads from SMEM, more scoped VMEM
than the chip allows. Here each ``kernels.ops`` wrapper is lowered with
``interpret=False`` against a described (not attached) ``v5e:2x2``
topology, at the planner's own tiles, and compiled by the TPU compiler
installed with JAX; no chip is needed. The shapes are the kernels' real
regimes (d=128 and the paper's d=512 fit) and the shapes ``chip_smoke.py``
runs.

The topology is described inside module-scoped fixtures (never at import)
and the tests skip where it cannot be described.

The compiled HLO also carries the names a device trace is read by: each
kernel's explicit ``pallas_call`` name, and the ``ivf.probe``,
``ivf.gather`` and ``ivf.scan`` scopes of the search program.
"""
import math
import re
import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8

# case id -> (ops wrapper, argument (shape, dtype) list, static kwargs)
CASES = {
    # k-means fit: FlashAssign, sort-inverse update, fused FlashLloyd
    "assign-N65536-K1024-d128": (
        "flash_assign", [((65536, 128), F32), ((1024, 128), F32)], {}),
    "assign-N1M-K65536-d512": (
        "flash_assign", [((1 << 20, 512), F32), ((65536, 512), F32)], {}),
    "update-N65536-K1024-d128": (
        "sort_inverse_update", [((65536, 128), F32), ((65536,), I32)],
        {"k": 1024}),
    "update-N8192-K65536-d512": (
        "sort_inverse_update", [((8192, 512), F32), ((8192,), I32)],
        {"k": 65536}),
    "lloyd-N65536-K256-d128": (
        "flash_lloyd_step", [((65536, 128), F32), ((256, 128), F32)], {}),
    "lloyd-N8M-K1024-d128": (
        "flash_lloyd_step", [((1 << 23, 128), F32), ((1024, 128), F32)], {}),
    # search: cell probe, grouped fp32 scan / rescore, quantized scan
    "probe-Q1024-K1024-l32": (
        "flash_probe", [((1024, 128), F32), ((1024, 128), F32)], {"l": 32}),
    "probe-Q128-K1024-l32": (
        "flash_probe", [((128, 128), F32), ((1024, 128), F32)], {"l": 32}),
    "scan-B128-C2048-l40": (
        "flash_probe_grouped", [((128, 128), F32), ((128, 2048, 128), F32)],
        {"l": 40}),
    "scan-B128-C65536-l10": (
        "flash_probe_grouped", [((128, 128), F32), ((128, 65536, 128), F32)],
        {"l": 10}),
    "scan-B128-C68800-l10": (   # ragged candidate axis (32 x 2150)
        "flash_probe_grouped", [((128, 128), F32), ((128, 68800, 128), F32)],
        {"l": 10}),
    "rescore-B128-R40-l10": (
        "flash_probe_grouped", [((128, 128), F32), ((128, 40, 128), F32)],
        {"l": 10}),
    "q8-B128-P32-W64-l40": (
        "flash_probe_grouped_q8",
        [((128, 32, 128), F32), ((128, 32, 64, 128), I8),
         ((128, 32, 64), F32)],
        {"l": 40}),
    "q8-B128-P32-W2048-l40": (
        "flash_probe_grouped_q8",
        [((128, 32, 128), F32), ((128, 32, 2048, 128), I8),
         ((128, 32, 2048), F32)],
        {"l": 40}),
    # list-major scan at the search_backlog unit: 128 x 32 pairs over
    # K=1024 lists in 1,280 segments of G=16, a padded (1024, 3000, 128)
    # store in 2048-row tiles, and the same unit over a paged pool of
    # 64-row pages (47 per segment)
    "lists-S1280-G16-padded-W3000-l10": (
        "flash_scan_lists",
        [((1280, 16, 128), F32), ((1024, 3000, 128), F32), ((1280,), I32),
         ((1280,), I32)],
        {"l": 10, "block_w": 2048}),
    "lists-S1280-G16-paged-P64-l10": (
        "flash_scan_lists",
        [((1280, 16, 128), F32), ((16384, 64, 128), F32), ((1280,), I32),
         ((1280, 47), I32)],
        {"l": 10, "block_w": 64}),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out entirely."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, specs, kw = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    with warnings.catch_warnings():
        # the planner's tiles must fit as planned: an audit auto-shrink
        # warning would mean the footprint model let through a bad tile
        warnings.simplefilter("error")
        lowered = getattr(ops, fn).lower(*args, interpret=False, **kw)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


# one small case per kernel: its pallas_call name, the ops wrapper and args
NAMED = {
    "flash_assign": ("flash_assign",
                     [((4096, 128), F32), ((256, 128), F32)], {}),
    "sort_inverse_update": ("sort_inverse_update",
                            [((4096, 128), F32), ((4096,), I32)],
                            {"k": 256}),
    "flash_lloyd_step": ("flash_lloyd_step",
                         [((4096, 128), F32), ((256, 128), F32)], {}),
    "flash_probe": ("flash_probe",
                    [((128, 128), F32), ((1024, 128), F32)], {"l": 32}),
    "flash_probe_grouped": ("flash_probe_grouped",
                            [((128, 128), F32), ((128, 2048, 128), F32)],
                            {"l": 10}),
    "flash_probe_grouped_q8": (
        "flash_probe_grouped_q8",
        [((128, 32, 128), F32), ((128, 32, 64, 128), I8),
         ((128, 32, 64), F32)], {"l": 40}),
    "flash_scan_lists": (
        "flash_scan_lists",
        [((64, 8, 128), F32), ((32, 512, 128), F32), ((64,), I32),
         ((64,), I32)], {"l": 10, "block_w": 256}),
}


def _custom_call_names(hlo: str) -> list[str]:
    """Instruction names of the compiled module's Mosaic kernels."""
    return [m.group(1) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo)]


@pytest.mark.parametrize("kernel", list(NAMED))
def test_kernel_hlo_carries_its_name(kernel, one_chip, no_compile_cache):
    fn, specs, kw = NAMED[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]

    @jax.jit
    def caller(*a):
        # the wrapper's body traced inside a jit of another name: an
        # unnamed pallas_call would take the caller's name
        return getattr(ops, fn).__wrapped__(*a, interpret=False, **kw)

    hlo = caller.lower(*args).compile().as_text()
    names = _custom_call_names(hlo)
    assert names and all(n.split(".")[0] == kernel for n in names), names


def test_ivf_search_hlo_carries_stage_scopes(one_chip, no_compile_cache):
    """The flat fp32 search program names its three stages, runs the
    probe and the list-major scan kernels, and holds no gathered
    ``(B, nprobe·width, d)`` candidate block: every f32 buffer in it is
    smaller than that block would be."""
    from repro.core import plan as _plan
    from repro.index import ivf
    b, k, d, cap, nprobe, topk = 128, 1024, 128, 256, 32, 10
    planner = _plan.default_planner()
    bqn, bqk = planner.plan("probe", (b, k, d, nprobe), F32).blocks
    g, bw = planner.plan("list_scan", (b * nprobe, k, cap, d, topk),
                         F32).blocks
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in
            [((b, d), F32), ((k, d), F32), ((k,), F32), ((k,), I32)]]
    store = tuple(jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                  for s, dt in [((k, cap, d), F32), ((k, cap), I32)])
    spec = ivf._SearchSpec(b=b, topk=topk, nprobe=nprobe, width=cap,
                           kind="padded", ps=0, nsh=1, k=k,
                           route_tiles=(bqn, bqk), scan_tiles=(g, bw),
                           interpret=False)
    hlo = ivf._ivf_search.lower(*args, store,
                                spec=spec).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for stage in ("ivf.probe", "ivf.gather", "ivf.scan"):
        assert any(f"/{stage}/" in n for n in op_names), stage
    names = _custom_call_names(hlo)
    assert sorted(n.split(".")[0] for n in names) == [
        "flash_probe", "flash_scan_lists"], names
    block = b * nprobe * cap * d
    sizes = [math.prod(int(v) for v in dims.split(",") if v)
             for dims in re.findall(r"\bf32\[([0-9,]*)\]", hlo)]
    assert sizes and max(sizes) < block, max(sizes)
