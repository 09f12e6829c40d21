"""CPU tests of the benchmark harness, at sizes a test run holds:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They read their cells from BENCHMARK.json and everything particular to an
app model from files named for it, so a later cell is new files too:

    tiny/<config>.json    a partial configuration, deep-merged into the
                          configuration (traffic.merge), that cuts it to
                          a size a test run holds
    faults/<app model>.py `FAULTS`: {name: fault(monkeypatch)}, each a
                          break of the timed path underneath that the
                          comparison must catch

`tiny_root` is a copy of BENCHMARK.json and benchmark/ with every
configuration cut to its tiny size; `measure_tiny` runs a cell there
through the harness with its look for a TPU skipped.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import run, spec as spec_mod, traffic  # noqa: E402

TESTS = Path(__file__).resolve().parent
TINY = TESTS / "tiny"
FAULTS = TESTS / "faults"


def copy_bench(dst) -> None:
    shutil.copy(spec_mod.ROOT / "BENCHMARK.json", dst)
    shutil.copytree(spec_mod.HERE, dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def shrink(root, config: str, tiny=TINY) -> None:
    """Cut the configuration `config` of the tree at `root` to its tiny
    size, tiny/<config>.json."""
    (entry,) = [c for c in spec_mod.Spec.load(root).doc["configs"]
                if c["name"] == config]
    path = Path(root) / entry["file"]
    cut = json.loads((Path(tiny) / f"{config}.json").read_text())
    path.write_text(json.dumps(traffic.merge(json.loads(path.read_text()),
                                             cut)))


def cells() -> list[str]:
    """The cells of BENCHMARK.json."""
    return [c["name"] for c in spec_mod.Spec.load().doc["workloads"]]


def app_model(cell: str) -> str:
    sp = spec_mod.Spec.load()
    c = sp.cell(cell)
    return run._app_model(traffic.apply(sp.config(c), sp.traffic(c), 0))


def faults(app: str, where=FAULTS) -> dict:
    """The FAULTS of faults/<app>.py."""
    return spec_mod.load_module(Path(where) / f"{app}.py",
                                f"faults of {app!r}").FAULTS


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    copy_bench(root)
    for config in spec_mod.Spec.load(root).doc["configs"]:
        shrink(root, config["name"])
    return root


@pytest.fixture(scope="session")
def measure_tiny(tiny_root):
    import jax

    sp = spec_mod.Spec.load(tiny_root)

    def go(cell: str, seed: int = 2**31 + 11, seconds: float = 1.0):
        return run.measure(cell, seed, seconds, False, time.perf_counter(),
                           spec=sp, devices=lambda n: jax.devices())

    return go
