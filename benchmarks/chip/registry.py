"""Finds every piece of a cell by its name in ``BENCHMARK.json``.

  configs/<config>.json      the configuration as it is run
  reference/<config>.py      its plain reference
  traffic/<mix>.json         a traffic mix, read by ``traffic.py``
  metrics/<metric>.py        one reader per metric: ``read(ctx)``
  limits/<workload>.json     the limits of the comparison, per cell
  families/<family>.py       how the harness drives one family of the
                             program's cells (``ArchConfig.family``)

A new cell, configuration, mix or metric is new files and a new entry; no
file that is there changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.PurePosixPath("benchmarks/chip")


class Bench:
    def __init__(self, root):
        self.root = pathlib.Path(root)
        self.dir = self.root / BENCH_DIR
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, workload: str) -> dict | None:
        p = self.dir / "limits" / f"{workload}.json"
        return json.loads(p.read_text()) if p.exists() else None

    def reference(self, config: str):
        """The configuration's plain reference; None where it has none yet
        (a cell needs one, a CPU test of the harness does not)."""
        if not (self.dir / "reference" / f"{config}.py").exists():
            return None
        return load(self.dir / "reference", config)

    def metric(self, name: str):
        return load(self.dir / "metrics", name)

    def family(self, name: str):
        return load(self.dir / "families", name)

    def metrics_of(self, workload: str, trace: bool) -> list[dict]:
        """The cell's metrics: end-to-end ones without a trace, per-layer
        ones with it, each kept where its ``workloads`` (if any) names the
        cell."""
        group = self.spec["per_layer"] if trace else self.spec["end_to_end"]
        return [m for m in group if workload in m.get("workloads", [workload])]


def load(directory: pathlib.Path, name: str):
    """Import ``<directory>/<name>.py`` (names may hold ``-`` and ``.``)."""
    path = directory / f"{name}.py"
    if str(directory) not in sys.path:
        sys.path.insert(0, str(directory))
    spec = importlib.util.spec_from_file_location(f"_bench_{directory.name}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
