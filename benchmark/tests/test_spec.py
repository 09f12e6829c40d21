"""BENCHMARK.json against the benchmark's own rules, and everything a cell
needs found by name, including a configuration, a mix and a metric added
as new files only."""

from __future__ import annotations

import json
import re
import subprocess
import sys

from benchmark import run, spec as spec_mod, traffic
from benchmark.tests.conftest import copy_bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_shape():
    doc = json.loads((spec_mod.ROOT / "BENCHMARK.json").read_text())
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in doc[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["per_layer"]:
        assert m["moves"] in e2e
    cells = {c["name"] for c in doc["workloads"]}
    for m in doc["per_layer"]:
        assert set(m["workloads"]) <= cells
    for c in doc["configs"]:
        assert c["file"].startswith(doc["paths"][0] + "/")


def test_every_cell_resolves():
    sp = spec_mod.Spec.load()
    for cell in sp.doc["workloads"]:
        cfg = traffic.apply(sp.config(cell), sp.traffic(cell), 2**40 + 3)
        assert cfg["general"]["seed"] == 2**40 + 3
        assert not set(spec_mod.META_KEYS) & set(cfg)
        assert traffic.warm_until_ns(sp.traffic(cell)) > 10**9
        assert run._app_model(cfg) == "phold"
        for m in sp.metrics(True, cell):
            assert callable(sp.reader(m["name"]))
        assert [m["name"] for m in sp.metrics(False, cell)] == [
            "sim_s_per_wall_s", "setup_s"]


def test_new_config_mix_and_metric_are_files_only(tmp_path):
    """A later PR adds a configuration, a mix and a per-layer metric as new
    files plus entries in BENCHMARK.json; no existing file changes."""
    copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    b = tmp_path / "benchmark"
    cfg = json.loads((b / "configs" / "phold_16k.json").read_text())
    cfg["hosts"]["peer"]["quantity"] = 1024
    (b / "configs" / "phold_1k.json").write_text(json.dumps(cfg))
    (b / "traffic" / "phold_1k.hot.json").write_text(json.dumps({
        "why": "skewed", "warm_until": "3 s", "windows_per_dispatch": 16,
        "config": {"hosts": {"peer": {"app_options": {
            "hot_frac": 0.1, "hot_share": 0.6}}}}}))
    (b / "layers" / "setup.twice_s.py").write_text(
        "def read(run):\n    return 2 * run.setup_s\n")
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "phold_1k", "source": "x",
                           "file": "benchmark/configs/phold_1k.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "phold_1k.hot", "config": "phold_1k",
                             "traffic": "hot", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "setup.twice_s", "unit": "s",
                             "better": "lower", "source": "host_clock",
                             "layer": "builders", "moves": "setup_s",
                             "workloads": ["phold_1k.hot"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    sp = spec_mod.Spec.load(tmp_path)
    cell = sp.cell("phold_1k.hot")
    cfg = traffic.apply(sp.config(cell), sp.traffic(cell), 9)
    assert cfg["hosts"]["peer"]["quantity"] == 1024
    assert cfg["hosts"]["peer"]["app_options"]["hot_share"] == 0.6
    assert cfg["hosts"]["peer"]["app_options"]["msgload"] == 1
    assert [m["name"] for m in sp.metrics(True, cell)] == ["setup.twice_s"]
    assert sp.reader("setup.twice_s")(type("R", (), {"setup_s": 2.5})) == 5
    # the old cells see none of it
    assert "setup.twice_s" not in [
        m["name"] for m in sp.metrics(True, sp.cell("phold_16k.msgload8"))]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_refuses_without_tpu(tmp_path):
    """On the CPU the harness exits 2 and prints no result line."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmark", "--workload",
         "phold_16k.msgload8", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=spec_mod.ROOT, capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_refuses_in_a_bare_checkout(tmp_path):
    """Only BENCHMARK.json and benchmark/: no program, no result."""
    copy_bench(tmp_path)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark", "--workload",
         "phold_16k.msgload8", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(tmp_path)})
    assert p.returncode != 0
    assert p.stdout == ""
