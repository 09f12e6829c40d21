"""CPU tests of the benchmark harness, at sizes a test run holds:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

`tiny_root` is a copy of BENCHMARK.json and benchmark/ whose
configuration is cut to a few hundred hosts; `measure_tiny` runs a cell
there through the harness with its look for a TPU skipped.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import spec as spec_mod  # noqa: E402

TINY = {"phold_16k": (256, 4096)}


def copy_bench(dst) -> None:
    shutil.copy(spec_mod.ROOT / "BENCHMARK.json", dst)
    shutil.copytree(spec_mod.HERE, dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def shrink(root, config: str, hosts: int, capacity: int) -> None:
    path = root / "benchmark" / "configs" / f"{config}.json"
    cfg = json.loads(path.read_text())
    cfg["hosts"]["peer"]["quantity"] = hosts
    cfg["experimental"]["event_capacity"] = capacity
    path.write_text(json.dumps(cfg))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    copy_bench(root)
    for name, (hosts, cap) in TINY.items():
        shrink(root, name, hosts, cap)
    return root


@pytest.fixture(scope="session")
def measure_tiny(tiny_root):
    import jax

    from benchmark import run

    sp = spec_mod.Spec.load(tiny_root)

    def go(cell: str, seed: int = 2**31 + 11, seconds: float = 1.0):
        return run.measure(cell, seed, seconds, False, time.perf_counter(),
                           spec=sp, devices=lambda n: jax.devices())

    return go
