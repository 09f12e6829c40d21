"""The arithmetic of the metrics: the rate over the whole window, the
operation counts, and the per-layer readers on a hand-made run."""

from __future__ import annotations

import pytest

from benchmark import run, spec as spec_mod, tracing


def test_rate_is_over_the_whole_window():
    win = run.Window(seconds=10, trace_dir=None, t0=100.0)
    win.boundaries = [run.Boundary(101.0, 8 * 10**9),
                      run.Boundary(105.0, 20 * 10**9),
                      run.Boundary(110.5, 35 * 10**9)]
    # 30 simulated s from the window's start frontier, over 10.5 wall s:
    # not a mean of the dispatches' own rates
    assert run.sim_rate(win, 5 * 10**9) == pytest.approx(30 / 10.5)


def test_operations_count_capacity_drops_only():
    before = {"events_committed": 100, "pool_overflow_dropped": 1,
              "outbox_overflow_dropped": 0, "packets_dropped_loss": 3,
              "inbox_overflow_deferred": 2}
    after = {"events_committed": 1100, "pool_overflow_dropped": 4,
             "outbox_overflow_dropped": 2, "packets_dropped_loss": 50,
             "inbox_overflow_deferred": 90}
    assert run.operations(before, after, {}, {}) == (1005, 5)


def test_operations_count_the_references_capacity_drops():
    """The growth of each CAPACITY counter over the window is failed, and
    attempted; the model's own drops (loss, CoDel) are not."""
    ring, sendq = "subs.router.overflow_dropped", "subs.nic.sendq_dropped"
    before = {"events_committed": 100, "pool_overflow_dropped": 1,
              "outbox_overflow_dropped": 0, "packets_dropped_loss": 3,
              "subs.router.codel_dropped": 5}
    after = {"events_committed": 1100, "pool_overflow_dropped": 4,
             "outbox_overflow_dropped": 2, "packets_dropped_loss": 50,
             "subs.router.codel_dropped": 60}
    assert run.operations(before, after, {ring: 7, sendq: 0},
                          {ring: 27, sendq: 11}) == (1036, 36)
    assert run.operations(before, after, {ring: 7}, {ring: 27}) == (1025, 25)
    assert run.operations(before, after, {}, {}) == (1005, 5)


def _record(**kw):
    sp = spec_mod.Spec.load()
    summary = tracing.TraceSummary(
        window_s=2.0, busy_s=1.5, devices=1,
        modules={"jit_run_to": [tracing.Execution(0, 10, 10**9)] * 4,
                 "jit_other": [tracing.Execution(0, 10, 5 * 10**9)] * 4})
    base = dict(
        cell=sp.cell("phold_16k.msgload8"), spec=sp,
        device_kind="TPU v5 lite", setup_s=30.0, build_s=4.0, compile_s=6.0,
        spans=[("host_drain", 0.5, 0.6), ("dispatch", 0.6, 1.5),
               ("host_drain", 1.5, 1.8), ("host_drain", 2.9, 3.2)],
        traced=[run.Boundary(0.0, 0, 0, 0), run.Boundary(1.0, 0, 640, 64),
                run.Boundary(3.0, 0, 1280, 128)],
        trace=summary)
    base.update(kw)
    return run.RunRecord(**base)


def read(metric, rec):
    return spec_mod.Spec.load().reader(metric)(rec)


def test_layer_readers():
    rec = _record()
    assert read("setup.build_s", rec) == 4.0
    assert read("setup.compile_s", rec) == 6.0
    # host_drain inside [0, 3]: 0.1 + 0.3 + 0.1 of 3 s
    assert read("driver.host_drain_share", rec) == pytest.approx(50 / 3)
    assert read("device.idle_share", rec) == pytest.approx(25.0)
    # executions keep the middle two of four `run_to` ones: 1 s each, over
    # 64 windows per dispatch
    assert read("kernel.ms_per_window", rec) == pytest.approx(1e3 / 64)
    # 640 events x 64 B per dispatch in 1 s against 819 GB/s
    assert read("window_kernel_roofline", rec) == pytest.approx(
        100 * 640 * 64 / 819e9)


def test_readers_find_nothing_without_a_trace():
    rec = _record(traced=[], trace=None)
    for m in ("driver.host_drain_share", "device.idle_share",
              "kernel.ms_per_window", "window_kernel_roofline"):
        assert read(m, rec) is None


def test_roofline_needs_its_configurations_row_size():
    """A configuration with no tables/event_bytes/<config>.json has no
    roofline to read, rather than one counted from another's rows."""
    rec = _record(cell={"name": "x.y", "config": "x", "traffic": "y"})
    assert read("kernel.ms_per_window", rec) == pytest.approx(1e3 / 64)
    assert read("window_kernel_roofline", rec) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        read("window_kernel_roofline", _record(device_kind="TPU v9"))
