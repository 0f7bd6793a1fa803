"""Parts are found by name from files alone: a new cell, configuration,
mix and metric need new files and BENCHMARK.json entries, and no edit to
a file that is there."""
import json
import os
import shutil

from conftest import BENCH
from harness.spec import Registry


def test_new_parts_from_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    reg0 = Registry(root)
    n_cells = len(reg0.cell_names())
    # a throwaway configuration, mix, cell and metric, as new files ...
    b = root / "bench"
    (b / "configs" / "new-model.json").write_text(json.dumps({"x": 1}))
    (b / "traffic" / "newmix.json").write_text(json.dumps({"y": 2}))
    (b / "cells" / "new.cell.json").write_text(json.dumps(
        {"config": "new-model", "traffic": "newmix"}))
    (b / "metrics" / "new_metric.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    # ... and entries appended to BENCHMARK.json
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "new.cell", "config": "new-model",
                               "traffic": "newmix", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "new_metric.x", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "t", "moves": "itl_p95_ms",
                               "workloads": ["new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    reg = Registry(root)
    assert "new.cell" in reg.cell_names()
    assert len(reg.cell_names()) == n_cells + 1
    cell = reg.cell("new.cell")
    assert reg.config(cell["config"]) == {"x": 1}
    assert reg.mix(cell["traffic"]) == {"y": 2}
    assert reg.workload("new.cell")["chips"] == 1
    names = [m["name"] for m in reg.metrics_for("new.cell", "per_layer")]
    assert names == ["new_metric.x"]
    assert reg.reader("new_metric.x")(None) == 42.0
    # every metric without a workloads key reaches the new cell too
    e2e = [m["name"] for m in reg.metrics_for("new.cell", "end_to_end")]
    assert "setup_s" in e2e and "ttft_p50_ms" not in e2e
    # no file that was there changed, BENCHMARK.json aside
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p


def test_every_listed_part_has_its_file():
    reg = Registry()
    bench = reg.benchmark()
    for w in bench["workloads"]:
        cell = reg.cell(w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        reg.config(cell["config"])
        reg.mix(cell["traffic"])
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(reg.reader(m["name"]))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(reg.root, c["file"]))
        assert reg.config(c["name"])["source"] == c["source"]
