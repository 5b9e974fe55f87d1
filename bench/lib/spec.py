"""The benchmark's declarations: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own and is found by name:

  configuration  ``bench/configs/<config>.json`` (the ``file`` of its entry)
  traffic mix    ``bench/traffic/<traffic>.json``
  metric         ``bench/metrics/<metric>.py``, a module with ``read(run)``

so a later change adds a cell, a mix or a metric by adding files and
entries, never by editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass
class Spec:
    raw: dict
    root: Path

    @property
    def bench_dir(self) -> Path:
        return self.root / self.raw["paths"][0]

    def workload(self, name: str) -> dict:
        for w in self.raw["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.raw["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench_dir / "traffic" / f"{name}.json").read_text())

    def metrics_for(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        e2e = [m for m in self.raw["end_to_end"] if _applies(m, cell)]
        if kind == "end_to_end":
            return e2e
        # a per-layer metric without a cell list goes to every cell that
        # reports the end-to-end metric it moves
        moved = {m["name"] for m in e2e}
        return [
            m
            for m in self.raw["per_layer"]
            if _applies(m, cell) and m["moves"] in moved
        ]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
        path = self.bench_dir / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: Path = ROOT) -> Spec:
    return Spec(json.loads((Path(root) / "BENCHMARK.json").read_text()), Path(root))
