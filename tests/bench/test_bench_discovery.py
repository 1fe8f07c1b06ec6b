"""A configuration, a cell and a per-layer metric are found by name from
files of their own: added to a copy of the benchmark, they are found
without an edit to any harness file."""
from __future__ import annotations

import json
import os
import shutil

from bench import cells

ROOT = os.path.dirname(cells.BENCH)


def _copy(tmp_path):
    dst = tmp_path / "checkout"
    shutil.copytree(cells.BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    return dst


def test_every_cell_loads_with_its_parts():
    bm = cells.benchmark()
    for w in bm["workloads"]:
        cell = cells.load_cell(w["name"])
        wl = cell["workload"]
        assert (wl["config"], wl["traffic"], wl["chips"], wl["why"]) == \
            (w["config"], w["traffic"], w["chips"], w["why"])
        assert hasattr(cells.driver(wl["driver"]), "window")
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(cells.layer_reader(m["name"]))
    for c in bm["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_added_files_are_found_without_editing_the_harness(tmp_path):
    dst = _copy(tmp_path)
    bench = str(dst / "bench")
    before = {p: open(os.path.join(bench, p)).read()
              for p in ("cells.py", "run.py", "trace.py", "compare.py")}
    cfg = json.load(open(os.path.join(bench, "configs", "ivf_sift1m.json")))
    cfg.update(name="ivf_deep10m", d=96, n=10_000_000)
    json.dump(cfg, open(os.path.join(bench, "configs", "ivf_deep10m.json"),
                        "w"))
    wl = json.load(open(os.path.join(bench, "workloads",
                                     "search_backlog.json")))
    wl.update(config="ivf_deep10m", traffic="backlog_deep")
    json.dump(wl, open(os.path.join(bench, "workloads",
                                    "deep_backlog.json"), "w"))
    with open(os.path.join(bench, "layer_metrics", "probe_share.py"),
              "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    bm = json.load(open(dst / "BENCHMARK.json"))
    bm["configs"].append({"name": "ivf_deep10m", "source": "x",
                          "file": "bench/configs/ivf_deep10m.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "deep_backlog", "config": "ivf_deep10m",
                            "traffic": "backlog_deep", "chips": 1,
                            "why": "x"})
    for m in bm["end_to_end"]:
        if m["name"] == "search_qps":
            m["workloads"].append("deep_backlog")
    bm["per_layer"].append({"name": "probe_share", "unit": "%",
                            "better": "higher", "source": "device_trace",
                            "layer": "index", "moves": "search_qps"})
    json.dump(bm, open(dst / "BENCHMARK.json", "w"))

    cell = cells.load_cell("deep_backlog", bench)
    assert cell["config"]["d"] == 96
    assert cell["workload"]["driver"] == "backlog"
    assert [m["name"] for m in cell["end_to_end"]] == ["search_qps",
                                                       "setup_s"]
    # no workloads key: reported wherever its end-to-end metric is
    assert "probe_share" in [m["name"] for m in cell["per_layer"]]
    assert "probe_share" not in [
        m["name"] for m in cells.load_cell("fit_k1024_d128",
                                           bench)["per_layer"]]
    assert cells.layer_reader("probe_share", bench)({}) == 42.0
    assert before == {p: open(os.path.join(bench, p)).read()
                      for p in before}
