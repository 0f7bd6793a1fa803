"""Find the benchmark's parts by name.

Every part lives in a file of its own, so a later change adds a
configuration, a traffic mix, a cell or a metric by adding files and
``BENCHMARK.json`` entries, never by editing one that is there:

* ``BENCHMARK.json``                the contract: cells, metrics, bounds
* ``bench/configs/<config>.json``   published sizes, source, precision
* ``bench/traffic/<mix>.json``      parameters the one generator reads
* ``bench/cells/<cell>.json``       config, mix, engine settings, rate
* ``bench/metrics/<metric>.py``     a reader with ``read(run) -> float|None``
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class Registry:
    """The benchmark's files under one checkout root."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self._readers: dict = {}

    def benchmark(self) -> dict:
        return _load(self.root / "BENCHMARK.json")

    def cell_names(self) -> list[str]:
        return sorted(p.stem for p in (self.bench / "cells").glob("*.json"))

    def cell(self, name: str) -> dict:
        return _load(self.bench / "cells" / f"{name}.json")

    def config(self, name: str) -> dict:
        return _load(self.bench / "configs" / f"{name}.json")

    def mix(self, name: str) -> dict:
        return _load(self.bench / "traffic" / f"{name}.json")

    def workload(self, name: str) -> dict:
        """The cell's ``BENCHMARK.json`` entry (its chips live there)."""
        for w in self.benchmark()["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def metrics_for(self, cell: str, kind: str) -> list[dict]:
        """``kind`` is "end_to_end" or "per_layer": the metrics this cell
        reports, in ``BENCHMARK.json`` order (no ``workloads`` key: all)."""
        return [m for m in self.benchmark()[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """``bench/metrics/<metric>.py`` loaded by path (names hold dots)."""
        if metric not in self._readers:
            path = self.bench / "metrics" / f"{metric}.py"
            if not path.is_file():
                raise FileNotFoundError(f"no reader for metric {metric!r}: "
                                        f"{path}")
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{metric.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._readers[metric] = mod.read
        return self._readers[metric]


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)
