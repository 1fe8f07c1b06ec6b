"""Finding a cell's parts by name.

Everything that belongs to one cell, configuration, traffic driver or
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` or the cell's file gives:

- ``workloads/<cell>.json``: the configuration's name, the traffic
  driver's name and its parameters, the chips, the limits of the numbers
  that decide ``correct``;
- ``configs/<config>.json``: the deployment, its source and sizes;
- ``drivers/<driver>.py``: one module per kind of traffic;
- ``layer_metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(run) -> float | None``.

Which end-to-end and per-layer metrics a cell reports, and their units,
come from ``BENCHMARK.json`` beside the benchmark's directory.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(bench_dir: str = BENCH) -> dict:
    return _json(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def listed(name: str, bench_dir: str = BENCH) -> bool:
    """Whether ``BENCHMARK.json`` lists the cell ``name``."""
    return name in {w["name"] for w in benchmark(bench_dir)["workloads"]}


def load_cell(name: str, bench_dir: str = BENCH) -> dict:
    """The cell ``name`` with its configuration and the metrics it reports:
    ``{"name", "workload", "config", "end_to_end", "per_layer"}``. A
    workload file that ``BENCHMARK.json`` does not list loads too (with
    the metrics every cell reports), so its driver can be tried."""
    bm = benchmark(bench_dir)
    workload = _json(os.path.join(bench_dir, "workloads", f"{name}.json"))
    config = _json(os.path.join(bench_dir, "configs",
                                f"{workload['config']}.json"))
    e2e = [m for m in bm["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"] if _reports(m, name, names)]
    return {"name": name, "workload": workload, "config": config,
            "end_to_end": e2e, "per_layer": layer}


def driver(name: str):
    """The traffic driver module ``drivers/<name>.py``."""
    return importlib.import_module(f"bench.drivers.{name}")


def layer_reader(name: str, bench_dir: str = BENCH):
    """``read(run)`` of ``layer_metrics/<name>.py``."""
    path = os.path.join(bench_dir, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, bench_dir: str = BENCH) -> dict:
    """The chip's peaks; an unknown device is an error."""
    table = _json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in peaks.json")
    return table[device_kind]
