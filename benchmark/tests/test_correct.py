"""`correct` through the whole harness at a tiny size on the CPU: true on
sound runs, false with the timed path broken underneath, and false for
the control (the reference with its draws in bfloat16)."""

from __future__ import annotations

import json

import jax.numpy as jnp
import pytest

from benchmark import compare, control, traffic
from benchmark.spec import Spec
from benchmark.tests.conftest import copy_bench, shrink

CELLS = ["phold_16k.msgload8", "phold_16k.msgload1"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(measure_tiny, cell):
    out = measure_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["metrics"]["sim_s_per_wall_s"]["value"] > 0


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


def _half_batch(monkeypatch):
    """Events of odd hosts are taken from the pool and never handled."""
    from shadow_tpu.net import apps

    real_m = apps.PholdApp.handle_msg_matrix

    def matrix(self, state, mv, emitter, params):
        even = (state.host.gid % 2 == 0)[:, None]
        return real_m(self, state, mv.replace(mask=mv.mask & even),
                      emitter, params)

    monkeypatch.setattr(apps.PholdApp, "handle_msg_matrix", matrix)


def _answer_altered(monkeypatch):
    """A message goes to the next host over from where its sender's draw
    says."""
    from shadow_tpu.net import apps

    real_pick = apps.PholdApp._pick_dst

    def pick(self, u, my_id):
        d = real_pick(self, u, my_id)
        nxt = (d + 1) % self.num_hosts
        return jnp.where(nxt == my_id, d, nxt)

    monkeypatch.setattr(apps.PholdApp, "_pick_dst", pick)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(measure_tiny, monkeypatch, cell,
                                          fault):
    FAULTS[fault](monkeypatch)
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


@pytest.mark.parametrize("cell", CELLS)
def test_loss_rolls_match(tmp_path, cell):
    """At a loss rate where many messages drop, the reference still
    predicts every host exactly."""
    copy_bench(tmp_path)
    shrink(tmp_path, "phold_16k", 256, 4096)
    path = tmp_path / "benchmark" / "configs" / "phold_16k.json"
    cfg = json.loads(path.read_text())
    g = cfg["network"]["graph"]
    g["inline"] = g["inline"].replace("packet_loss 0.0", "packet_loss 0.002")
    path.write_text(json.dumps(cfg))
    import time

    import jax

    from benchmark import run

    out = run.measure(cell, 77, 1.0, False, time.perf_counter(),
                      spec=Spec.load(tmp_path),
                      devices=lambda n: jax.devices())
    assert out["correct"], out["checks"]
