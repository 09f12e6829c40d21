"""The window kernel's phases from a profiler trace: op scopes decoded from
an XSpace's event metadata, self time filed per `win.*` phase, and the
per-layer readers that report it."""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from benchmark import run, scopes, spec as spec_mod, tracing

MS = 10**6
DATA = Path(__file__).parent / "data"
ROOT = "jit(run_to)/while/body/cond/branch_1_fun"


def test_split_by_hand():
    sc = {"sort.1": f"{ROOT}/win.extract/sort",
          "fusion.2": f"{ROOT}/win.extract/jit(_take)/gather",
          "fusion.3": f"{ROOT}/win.handlers/add",
          "fusion.4": f"{ROOT}/win.commit/win.merge/win.commit/xor",
          "sort.5": f"{ROOT}/win.merge/sort",
          "fusion.6": "jit(run_to)/while/cond/reduce_sum"}
    ops = [("while.9", 0, 20 * MS),
           ("fusion.6", 0, 1 * MS),           # the loop's own control
           ("conditional.8", 1 * MS, 19 * MS),
           ("sort.1", 2 * MS, 5 * MS),
           ("copy.7", 5 * MS, 6 * MS),        # no op_name: extract's
           ("fusion.2", 6 * MS, 8 * MS),
           ("fusion.3", 8 * MS, 9 * MS),
           ("fusion.4", 9 * MS, 11 * MS),     # innermost scope wins
           ("sort.5", 11 * MS, 15 * MS),
           ("copy.10", 15 * MS, 16 * MS),     # no op_name: merge's
           ("sort.1", 30 * MS, 31 * MS)]      # outside the execution
    (per,) = scopes.split(ops, sc, [tracing.Execution(0, 20 * MS, 20 * MS)])
    assert per == {"win.extract": 6 * MS, "win.handlers": 1 * MS,
                   "win.commit": 2 * MS, "win.merge": 5 * MS,
                   # fusion.6, and the gaps inside while.9, conditional.8
                   "unscoped": 1 * MS + 1 * MS + 4 * MS}
    assert sum(per.values()) == 20 * MS


def test_no_scoped_op_reads_nothing():
    planes = [("/device:TPU:0", {"XLA Ops": [("sort.1", 0, 5)]})]
    ex = [tracing.Execution(0, 5, 5)]
    assert scopes.reduce_planes(
        planes, {"/device:TPU:0": {"sort.1": "jit(run_to)/sort"}}, ex) is None
    assert scopes.reduce_planes(planes, {}, ex) is None


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, val) -> bytes:
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    if isinstance(val, str):
        val = val.encode()
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def test_op_scopes_from_event_metadata():
    def stat_meta(i, name):  # an XPlane.stat_metadata map entry
        return _field(5, _msg((1, i), (2, _msg((1, i), (2, name)))))

    def event_meta(i, name, *stats):  # an XPlane.event_metadata entry
        return _field(4, _msg((1, i), (2, _msg(
            (1, i), (2, name), *[(5, s) for s in stats]))))

    def tf_op(v):
        return _msg((1, 1), (5, v))

    def program(p):
        return _msg((1, 2), (3, p))

    plane = b"".join([
        _field(2, "/device:TPU:0"),
        stat_meta(1, "tf_op"), stat_meta(2, "program_id"),
        event_meta(10, "jit_run_to(77)"),
        event_meta(11, "jit_other(78)"),
        event_meta(12, "%sort.3 = (s32[8]) sort(s32[8] %p)",
                   tf_op(f"{ROOT}/win.extract/sort:"), program(77)),
        # an op of another program, and one with no op_name
        event_meta(13, "%fusion.4 = s32[8] fusion(s32[8] %p)",
                   tf_op(f"{ROOT}/win.merge/sort:"), program(78)),
        event_meta(14, "%copy.5 = s32[8] copy(s32[8] %p)", program(77)),
    ])
    space = _field(1, plane) + _field(1, _msg((2, "/host:CPU")))
    assert scopes.op_scopes(space) == {
        "/device:TPU:0": {"sort.3": f"{ROOT}/win.extract/sort"}}


@pytest.fixture(scope="module")
def recorded():
    """Three `run_to` executions of a 512-host PHOLD traced on a TPU v5
    lite by the harness, with the op_name of each op of the program."""
    with gzip.open(DATA / "phold_512.scoped.json.gz", "rt") as f:
        doc = json.load(f)
    planes = [(n, {k: [tuple(e) for e in v] for k, v in lines.items()})
              for n, lines in doc["planes"]]
    return planes, doc["scopes"], tracing.reduce_planes(planes)


def test_recorded_phases_sum_to_the_execution(recorded):
    planes, sc, summary = recorded
    (mid,) = summary.executions("run_to")
    per = scopes.reduce_planes(planes, sc, [mid])
    assert sum(per.values()) == pytest.approx(mid.busy_ns, rel=1e-9)
    assert all(per[p] > 0 for p in scopes.PHASES)
    # the loop's control is a small part of the 512-host kernel
    assert per[scopes.UNSCOPED] < 0.15 * mid.busy_ns
    assert per["win.extract"] == max(per.values())


def test_recorded_sorts_lie_in_extract_or_merge(recorded):
    planes, sc, summary = recorded
    ops = planes[0][1]["XLA Ops"]
    phase = {op: ph for op, ph, _ in scopes.self_times(ops, sc["/device:TPU:0"])}
    sorts = {op: ph for op, ph in phase.items() if op.startswith("sort")}
    assert sorts and set(sorts.values()) <= {"win.extract", "win.merge"}
    # every op of the breakdown is filed in a phase or the loop's control
    for op, _ in summary.device_ops:
        assert phase[op] in scopes.PHASES or op.startswith(scopes.CONTROL)


def _record(tmp_path, **kw):
    sp = spec_mod.Spec.load()
    summary = tracing.TraceSummary(
        window_s=2.0, busy_s=1.5, devices=1,
        modules={"jit_run_to": [tracing.Execution(0, 10, 10**9)] * 4})
    base = dict(
        cell=sp.cell("phold_16k.msgload8"), spec=spec_mod.Spec(sp.doc,
                                                               tmp_path),
        device_kind="TPU v5 lite", setup_s=30.0, build_s=4.0, compile_s=6.0,
        spans=[("dispatch", 0.0, 1.0), ("wait", 0.1, 0.9),
               ("fetch", 0.9, 0.902), ("await", 1.0, 2.0),
               ("wait", 1.0, 1.996), ("fetch", 1.996, 2.0)],
        traced=[run.Boundary(0.0, 0, 0, 0), run.Boundary(1.0, 0, 640, 64),
                run.Boundary(3.0, 0, 1280, 128)],
        trace=summary)
    base.update(kw)
    return run.RunRecord(**base)


NEW = ("kernel.extract_ms_per_window", "kernel.handlers_ms_per_window",
       "kernel.commit_ms_per_window", "kernel.merge_ms_per_window",
       "kernel.unscoped_ms_per_window")


def test_readers(tmp_path):
    sp = spec_mod.Spec.load()
    rec = _record(tmp_path)
    assert sp.reader("driver.fetch_ms_per_dispatch")(rec) == pytest.approx(3.0)
    assert sp.reader("driver.fetch_ms_per_dispatch")(
        _record(tmp_path, spans=[("await", 0.0, 1.0)])) is None
    # no trace file, as a program without the scopes gives none to read
    for name in NEW:
        assert sp.reader(name)(rec) is None
    entries = {m["name"]: m for m in sp.doc["per_layer"]}
    for name in NEW + ("driver.fetch_ms_per_dispatch",):
        assert entries[name]["moves"] == "sim_s_per_wall_s"
        # read from any cell's spans and window kernel, so every cell
        # reports it, those added later too
        assert "workloads" not in entries[name]
