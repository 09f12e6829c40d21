"""`correct` through the whole harness at a tiny size on the CPU: true on
sound runs, false with the timed path broken underneath, and false for
the control (the reference's `control`). Every cell of BENCHMARK.json
runs; faults particular to an app model come from faults/<app>.py."""

from __future__ import annotations

import json
import time

import jax.numpy as jnp
import pytest

from benchmark import compare, control, run, traffic
from benchmark.spec import Spec
from benchmark.tests.conftest import (app_model, cells, copy_bench, faults,
                                      shrink)

CELLS = cells()
PHOLD_CELLS = [c for c in CELLS if app_model(c) == "phold"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(measure_tiny, cell):
    out = measure_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["metrics"]["sim_s_per_wall_s"]["value"] > 0


def test_phold_operations_are_the_engine_counts(measure_tiny, monkeypatch):
    """PHOLD declares no CAPACITY counter: `attempted` and `failed` are
    the window's events committed plus pool and outbox drops, and those
    drops, as read from the engine's counters alone."""
    seen = []
    real = run.operations

    def spy(before, after, cap_before, cap_after):
        seen.append((before, after, cap_before, cap_after))
        return real(before, after, cap_before, cap_after)

    monkeypatch.setattr(run, "operations", spy)
    out = measure_tiny("phold_16k.msgload8")
    ((before, after, cap_before, cap_after),) = seen
    assert cap_before == cap_after == {}
    d = {k: after[k] - before[k] for k in after}
    dropped = d["pool_overflow_dropped"] + d["outbox_overflow_dropped"]
    assert out["failed"] == dropped == 0
    assert out["attempted"] == d["events_committed"] + dropped > 0


def _state_unchanged(monkeypatch):
    """Each dispatch of the window (the one run toward the far stop time)
    hands back the state it was given, its frontier advanced."""
    import jax

    from shadow_tpu.core import engine

    real = engine.make_run_to

    def make(step, hi):
        f = real(step, hi)

        def run_to(state, params, runahead, stop, max_windows):
            st, mn, press, occ = f(state, params, runahead, stop, max_windows)
            window = jnp.asarray(stop) > 10**13
            st = jax.tree.map(lambda a, b: jnp.where(window, a, b), state, st)
            return st, mn, press, occ

        return run_to

    monkeypatch.setattr(engine, "make_run_to", make)


def _faults(cell: str) -> dict:
    """Every cell's faults: the generic one and its app model's own."""
    return {"state_unchanged": _state_unchanged, **faults(app_model(cell))}


@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell in CELLS for fault in sorted(_faults(cell))])
def test_broken_timed_path_is_not_correct(measure_tiny, monkeypatch, cell,
                                          fault):
    _faults(cell)[fault](monkeypatch)
    out = measure_tiny(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell):
    sp = Spec.load(tiny_root)
    mix = sp.traffic(sp.cell(cell))
    frontier = traffic.warm_until_ns(mix) + 3 * 10**9
    for seed in (1, 2**31 + 5, 2**33 + 7):
        r = control.readings(cell, seed, frontier, spec=sp)
        assert compare.passed(r["sound"])
        assert not compare.passed(r["control"]), r


@pytest.mark.parametrize("cell", PHOLD_CELLS)
def test_loss_rolls_match(tmp_path, cell):
    """At a loss rate where many messages drop, the reference still
    predicts every host exactly."""
    copy_bench(tmp_path)
    sp = Spec.load(tmp_path)
    entry = sp.config_entry(sp.cell(cell))
    shrink(tmp_path, entry["name"])
    path = tmp_path / entry["file"]
    cfg = json.loads(path.read_text())
    g = cfg["network"]["graph"]
    g["inline"] = g["inline"].replace("packet_loss 0.0", "packet_loss 0.002")
    path.write_text(json.dumps(cfg))
    import jax

    out = run.measure(cell, 77, 1.0, False, time.perf_counter(),
                      spec=Spec.load(tmp_path),
                      devices=lambda n: jax.devices())
    assert out["correct"], out["checks"]
