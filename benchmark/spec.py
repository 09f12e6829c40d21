"""Find everything a cell needs by the names in BENCHMARK.json.

    configs/<config>.json         a Shadow configuration, with `source`,
                                  `reduced` and `assumed` beside it
    traffic/<config>.<mix>.json   the traffic mix (traffic.py reads it)
    layers/<metric>.py            one reader per per-layer metric
    reference/<app model>.py      the plain reference of an app model

A later cell, mix, configuration or metric is new files and new entries;
nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# keys of a configuration file that describe it and are not run
META_KEYS = ("source", "reduced", "assumed")


class SpecError(ValueError):
    pass


class Spec:
    def __init__(self, doc: dict, root: Path = ROOT):
        self.doc = doc
        self.root = Path(root)
        self.bench = self.root / doc["paths"][0]

    @classmethod
    def load(cls, root: Path = ROOT) -> "Spec":
        with open(Path(root) / "BENCHMARK.json") as f:
            return cls(json.load(f), root)

    def _named(self, section: str, name: str) -> dict:
        for entry in self.doc[section]:
            if entry["name"] == name:
                return entry
        raise SpecError(f"no {section} entry named {name!r}")

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config_entry(self, cell: dict) -> dict:
        return self._named("configs", cell["config"])

    def config(self, cell: dict) -> dict:
        """The Shadow configuration of the cell, without its meta keys."""
        with open(self.root / self.config_entry(cell)["file"]) as f:
            doc = json.load(f)
        return {k: v for k, v in doc.items() if k not in META_KEYS}

    def traffic(self, cell: dict) -> dict:
        path = self.bench / "traffic" / f"{cell['config']}.{cell['traffic']}.json"
        if not path.is_file():
            raise SpecError(f"cell {cell['name']!r}: no traffic file {path}")
        with open(path) as f:
            return json.load(f)

    def metrics(self, trace: bool, cell: dict) -> list[dict]:
        """The metrics the cell reports in a run with or without trace."""
        if not trace:
            return [m for m in self.doc["end_to_end"]
                    if cell["name"] in m.get("workloads", [cell["name"]])]
        return [m for m in self.doc["per_layer"]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric: str):
        """The `read(run)` function of layers/<metric>.py."""
        path = self.bench / "layers" / f"{metric}.py"
        if not path.is_file():
            raise SpecError(f"per-layer metric {metric!r}: no reader {path}")
        spec = importlib.util.spec_from_file_location(
            "benchmark_layer_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def table(self, name: str) -> dict:
        with open(self.bench / "tables" / f"{name}.json") as f:
            return json.load(f)
