"""The reduction from a profiler trace to busy time, idle gaps, device ops
and window-kernel executions."""

from __future__ import annotations

import pytest

from benchmark import tracing

MS = 10**6


def test_reduce_planes_by_hand():
    ops = [("sort.1", 0, 2 * MS), ("fusion.3", 2 * MS, 3 * MS),
           ("gather.2", 5 * MS, 6 * MS), ("sort.1", 6 * MS, 9 * MS),
           ("fusion.9", 12 * MS, 13 * MS)]
    mods = [("jit_run_to", 0, 4 * MS), ("jit_run_to", 5 * MS, 9 * MS),
            ("jit_run_to", 12 * MS, 13 * MS)]
    host = [("host_drain", 0, 1 * MS), ("await", 3 * MS, 5 * MS),
            ("host_drain", 9 * MS, 11 * MS), ("python_thing", 0, 20 * MS),
            ("issue", 11 * MS, 14 * MS)]
    planes = [("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": mods}),
              ("/host:CPU", {"python": host})]
    s = tracing.reduce_planes(planes)
    # traced span: first to last driver span, 0..14 ms
    assert s.window_s == pytest.approx(0.014)
    # busy: [0,3] + [5,9] + [12,13] = 8 ms
    assert s.busy_s == pytest.approx(0.008)
    assert s.device_ops[0] == ["sort.1", pytest.approx(0.005)]
    # gaps: [3,5] in await, [9,12] in host_drain/issue, [13,14] in issue
    assert s.idle_gaps[0] == ["host_drain", pytest.approx(0.003)]
    assert ["await", pytest.approx(0.002)] in s.idle_gaps
    # the middle execution only; its ops [5,6] and [6,9]
    (mid,) = s.executions("run_to")
    assert mid.busy_ns == 4 * MS


def test_self_time_of_nested_ops():
    ops = [("while.1", 0, 10), ("fusion.2", 1, 3), ("sort.3", 4, 8),
           ("gather.4", 5, 6), ("fusion.5", 12, 13)]
    assert sorted(tracing.self_times(ops)) == [
        ("fusion.2", 2), ("fusion.5", 1), ("gather.4", 1), ("sort.3", 3),
        ("while.1", 4)]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce_planes([("/host:CPU", {"python": []})])


def test_union_and_clip():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_recorded_chip_trace():
    """Three dispatches of a 512-host PHOLD traced on a TPU v5 lite by the
    harness (device ops and the driver's spans in their span), reduced."""
    import gzip
    import json
    from pathlib import Path

    path = Path(__file__).parent / "data" / "phold_512.trace.json.gz"
    with gzip.open(path, "rt") as f:
        planes = json.load(f)
    s = tracing.reduce_planes([(n, {k: [tuple(e) for e in v]
                                    for k, v in lines.items()})
                               for n, lines in planes])
    assert s.window_s == pytest.approx(0.106629722)
    assert s.busy_s == pytest.approx(0.091883856)
    (mid,) = s.executions("run_to")
    assert 0.030 < mid.busy_ns / 1e9 <= (mid.end - mid.start) / 1e9
    # self times: the window sorts lead, not the loops that hold them
    names = [n for n, _ in s.device_ops]
    assert names[0] == "sort.76" and not any(
        n.startswith(("while", "conditional")) for n in names[:3])
    assert {lab for lab, _ in s.idle_gaps[:3]} <= {"await", "issue"}
