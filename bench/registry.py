"""Finds a cell's configuration, traffic mix, generator and per-layer
metric readers by name.

``BENCHMARK.json`` at the checkout root names every cell, configuration
and metric. Each configuration, mix, graph generator and metric reader is
a file of its own under the benchmark directory (the first of ``paths``):

- ``configs/<name>.json`` (the file the configuration entry names),
- ``traffic/<name>.json``,
- ``limits/<configuration>.json``: the limits of the numbers that decide
  ``correct`` on that configuration's cells,
- ``graphs/<generator>.py`` with ``generate(params) -> (n, row_ptr, col_idx)``,
- ``metrics/<metric name>.py`` with ``read(run) -> float | None``.

A later cell, mix or metric is added by adding files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent


def load_module(path: Path, name: str):
    """Imports one file as a module of its own (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` of a checkout, and the files it names."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.spec["paths"][0]
        self.cache_dir = self.dir / ".cache"

    def _named(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"BENCHMARK.json has no {key} entry named {name!r}")

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._named("configs", name)
        cfg = json.loads((self.root / entry["file"]).read_text())
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> dict:
        mix = json.loads(self._file("traffic", name).read_text())
        mix["name"] = name
        return mix

    def limits(self, config: str) -> dict:
        return json.loads(self._file("limits", config).read_text())

    def _file(self, kind: str, name: str) -> Path:
        path = self.dir / kind / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no such file: {path}")
        return path

    def generator(self, name: str) -> Callable:
        return load_module(self.dir / "graphs" / f"{name}.py",
                           "graphs." + name).generate

    def end_to_end(self, cell: str) -> List[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[dict]:
        """The per-layer metrics this cell reports with ``--trace 1``: those
        that list the cell, and those without a list whose end-to-end
        metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str) -> Callable[[Dict], Optional[float]]:
        return load_module(self.dir / "metrics" / f"{metric}.py",
                           "metrics." + metric).read
