"""Find everything a cell needs by the names in BENCHMARK.json.

    configs/<config>.json         a Shadow configuration, with `source`,
                                  `reduced` and `assumed` beside it
    traffic/<config>.<mix>.json   the traffic mix (traffic.py reads it)
    layers/<metric>.py            one reader per per-layer metric
    reference/<app model>.py      the plain reference of an app model,
                                  to the protocol of reference/__init__.py
    tables/event_bytes/<config>.json  bytes one committed event moves

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
        self._references: dict = {}

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
        return load_module(self.bench / "layers" / f"{metric}.py",
                           f"per-layer metric {metric!r}").read

    def reference(self, app_model: str):
        """The module reference/<app model>.py of this tree, loaded once
        per Spec (its jitted programs then trace once)."""
        if app_model not in self._references:
            self._references[app_model] = load_module(
                self.bench / "reference" / f"{app_model}.py",
                f"app model {app_model!r}")
        return self._references[app_model]

    def table(self, name: str) -> dict:
        with open(self.bench / "tables" / f"{name}.json") as f:
            return json.load(f)


def load_module(path: Path, what: str = "module"):
    """A module of a benchmark tree, by its file path, so that the tree's
    own file runs and not the importable package's."""
    path = Path(path)
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    name = f"benchmark_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
