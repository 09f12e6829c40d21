"""BENCHMARK.json against the benchmark's own rules, and everything a cell
needs found by name, including a configuration, a mix, a metric and an
app model's reference added as new files only."""

from __future__ import annotations

import json
import re
import shutil
import subprocess
import sys
import time

from benchmark import compare, control, run, spec as spec_mod, traffic
from benchmark.tests.conftest import FAULTS, TINY, copy_bench, faults, shrink

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
        assert set(m.get("workloads", [])) <= cells
    for c in doc["configs"]:
        assert c["file"].startswith(doc["paths"][0] + "/")


def _holds_protocol(ref) -> None:
    """reference/__init__.py's protocol."""
    assert ref.FIELDS and all(isinstance(p, str) for p in ref.FIELDS.values())
    assert all(isinstance(p, str) for p in ref.TOTALS.values())
    assert isinstance(ref.CAPACITY, tuple)
    assert all(isinstance(p, str) for p in ref.CAPACITY)
    assert callable(ref.expected) and callable(ref.control)


def _cells_resolve(sp, tiny=TINY, faults_dir=FAULTS) -> None:
    for cell in sp.doc["workloads"]:
        cfg = traffic.apply(sp.config(cell), sp.traffic(cell), 2**40 + 3)
        assert cfg["general"]["seed"] == 2**40 + 3
        assert not set(spec_mod.META_KEYS) & set(cfg)
        assert traffic.warm_until_ns(sp.traffic(cell)) > 10**9
        app = run._app_model(cfg)
        _holds_protocol(sp.reference(app))
        assert faults(app, faults_dir)
        for m in sp.metrics(True, cell):
            assert callable(sp.reader(m["name"]))
        assert [m["name"] for m in sp.metrics(False, cell)] == [
            "sim_s_per_wall_s", "setup_s"]
    for config in sp.doc["configs"]:
        assert (tiny / f"{config['name']}.json").is_file()
        assert sp.table(f"event_bytes/{config['name']}")["bytes_per_event"]


def test_every_cell_resolves():
    _cells_resolve(spec_mod.Spec.load())


def _shared() -> list[str]:
    """The per-layer metrics BENCHMARK.json gives every cell."""
    return [m["name"] for m in spec_mod.Spec.load().doc["per_layer"]
            if "workloads" not in m]


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
    (b / "tables" / "event_bytes" / "phold_1k.json").write_text(
        (b / "tables" / "event_bytes" / "phold_16k.json").read_text())
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
    # the per-layer metrics of every cell, and its own
    assert [m["name"] for m in sp.metrics(True, cell)] == _shared() + [
        "setup.twice_s"]
    assert sp.reader("setup.twice_s")(type("R", (), {"setup_s": 2.5})) == 5
    # the old cells see none of it
    assert "setup.twice_s" not in [
        m["name"] for m in sp.metrics(True, sp.cell("phold_16k.msgload8"))]
    after = {p: p.read_bytes() for p in before}
    assert after == before


UDP_REFERENCE = """\
import numpy as np

from pathlib import Path

from benchmark.spec import load_module

common = load_module(Path(__file__).with_name("common.py"))

FIELDS = {"recv": "subs.udp_flood.recv"}
TOTALS = {"packets_sent": "counters.packets_sent"}
CAPACITY = ("subs.router.overflow_dropped", "subs.nic.sendq_dropped")


def expected(cfg, frontier_ns):
    H = len(common.hosts(cfg))
    return {"recv": np.zeros(H, np.int64), "pending": np.zeros(H, np.int64),
            "packets_sent": 0}


def control(cfg, frontier_ns):
    # A placeholder, for the harness to find: a real reference's control
    # is its prediction a step below the stated guarantee (for a flood,
    # the router ring unbounded or the token buckets' deficit ignored).
    out = expected(cfg, frontier_ns)
    out["recv"] = out["recv"] + 1
    return out
"""

UDP_FAULTS = """\
def receive_lost(monkeypatch):
    from shadow_tpu.net import apps

    monkeypatch.setattr(apps.UdpFloodApp, "on_receive",
                        lambda self, state, *args: state)


FAULTS = {"receive_lost": receive_lost}
"""


def test_new_app_model_is_files_only(tmp_path, monkeypatch):
    """A later PR adds a cell of another app model (here a UDP flood) as
    new files and entries: its configuration, mix, reference with CAPACITY
    and control, tiny size, faults and a per-layer reader. No existing
    file changes, the harness finds each by name, and a run counts the
    router ring's drops in `failed`."""
    copy_bench(tmp_path)
    b = tmp_path / "benchmark"
    tests = b / "tests"
    shutil.copytree(TINY, tests / "tiny")
    shutil.copytree(FAULTS, tests / "faults",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    # 7 clients of 11.76 Mbit/s into each server's 10 Mbit downlink: the
    # router's 8-slot ring overflows
    gml = ("graph [\n  node [ id 0 bandwidth_down \"10 Mbit\" "
           "bandwidth_up \"10 Mbit\" ]\n  edge [ source 0 target 0 latency "
           "\"1 ms\" packet_loss 0.0 ]\n]\n")
    (b / "configs" / "udp_flood_16.json").write_text(json.dumps({
        "source": "x", "reduced": [],
        "general": {"stop_time": "10 s", "seed": 1},
        "network": {"graph": {"type": "gml", "inline": gml}},
        "experimental": {"event_capacity": 4096, "router_queue_slots": 8},
        "hosts": {
            "server": {"quantity": 2, "app_model": "udp_flood",
                       "app_options": {"role": "server"}},
            "client": {"quantity": 14, "app_model": "udp_flood",
                       "app_options": {"interval": "1 ms",
                                       "size": 1470}}}}))
    (b / "traffic" / "udp_flood_16.dense.json").write_text(json.dumps({
        "why": "x", "warm_until": "2 s", "windows_per_dispatch": 16,
        "config": {"general": {"stop_time": "100001 s"},
                   "hosts": {"client": {"app_options": {
                       "runtime": "100000 s"}}}}}))
    (b / "reference" / "udp_flood.py").write_text(UDP_REFERENCE)
    (b / "tables" / "event_bytes" / "udp_flood_16.json").write_text(
        json.dumps({"row_bytes": 48, "payload_words": 6,
                    "bytes_per_event": 96}))
    (b / "layers" / "net.unbuilt_s.py").write_text(
        "def read(run):\n    return run.setup_s - run.build_s\n")
    (tests / "tiny" / "udp_flood_16.json").write_text(json.dumps(
        {"experimental": {"event_capacity": 1024}}))
    (tests / "faults" / "udp_flood.py").write_text(UDP_FAULTS)
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "udp_flood_16", "source": "x",
                           "file": "benchmark/configs/udp_flood_16.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "udp_flood_16.dense",
                             "config": "udp_flood_16", "traffic": "dense",
                             "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "net.unbuilt_s", "unit": "s",
                             "better": "lower", "source": "host_clock",
                             "layer": "builders", "moves": "setup_s",
                             "workloads": ["udp_flood_16.dense"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    sp = spec_mod.Spec.load(tmp_path)
    _cells_resolve(sp, tests / "tiny", tests / "faults")
    cell = sp.cell("udp_flood_16.dense")
    cfg = traffic.apply(sp.config(cell), sp.traffic(cell), 2**40 + 3)
    assert run._app_model(cfg) == "udp_flood"
    assert cfg["hosts"]["client"]["app_options"] == {
        "interval": "1 ms", "size": 1470, "runtime": "100000 s"}
    ref = sp.reference("udp_flood")
    assert ref.CAPACITY == ("subs.router.overflow_dropped",
                            "subs.nic.sendq_dropped")
    # PHOLD declares no CAPACITY, so nothing of its state is read
    assert compare.capacity(None, sp.reference("phold")) == {}
    # every per-layer metric of the old cells reaches the new one
    assert [m["name"] for m in sp.metrics(True, cell)] == _shared() + [
        "net.unbuilt_s"]
    assert sp.reader("net.unbuilt_s")(
        type("R", (), {"setup_s": 2.5, "build_s": 1.0})) == 1.5
    r = control.readings("udp_flood_16.dense", 7, 3 * 10**9, spec=sp)
    assert [c["value"] for c in r["sound"].values()] == [0, 0, 0]
    assert r["control"]["host_values_off"]["value"] == 16
    assert list(faults("udp_flood", tests / "faults")) == ["receive_lost"]
    # the old cells see none of it
    assert "net.unbuilt_s" not in [
        m["name"] for m in sp.metrics(True, sp.cell("phold_16k.msgload8"))]
    after = {p: p.read_bytes() for p in before}
    assert after == before

    # at its tiny size the cell runs through the harness, and the growth
    # of its CAPACITY counters over the window is `failed`
    shrink(tmp_path, "udp_flood_16", tests / "tiny")
    assert sp.config(cell)["experimental"] == {"event_capacity": 1024,
                                               "router_queue_slots": 8}
    seen = []
    real = run.operations

    def spy(before, after, cap_before, cap_after):
        seen.append({k: cap_after[k] - cap_before[k] for k in cap_after})
        return real(before, after, cap_before, cap_after)

    monkeypatch.setattr(run, "operations", spy)
    import jax

    out = run.measure("udp_flood_16.dense", 5, 1.0, False,
                      time.perf_counter(), spec=sp,
                      devices=lambda n: jax.devices())
    (drops,) = seen
    assert set(drops) == set(ref.CAPACITY)
    assert out["failed"] == sum(drops.values()) > 0
    assert out["attempted"] > out["failed"]


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
