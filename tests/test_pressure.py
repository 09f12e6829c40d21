"""Pressure plane (ISSUE 9): graceful degradation under device-memory and
pool exhaustion.

The acceptance gate: `exhaust_backend` / `saturate_pool` injections
across {conservative, optimistic} × {global, islands, fleet} end with
audit digest chains BIT-IDENTICAL to the uninterrupted run, with zero
bare RuntimeError/XlaRuntimeError escaping a driver — every terminal
pool stall is the typed `PoolExhausted` (core/pressure.py), raised only
after the degradation ladder gave up and the frontier drained to a
checkpoint. The chain (obs/audit.py) is the proof instrument: a ladder
rung that merely "looks right" cannot pass it.
"""

import os

import pytest

from shadow_tpu.core import pressure as pressure_mod
from shadow_tpu.core.pressure import (
    PoolExhausted,
    PressureController,
    PressurePolicy,
)
from shadow_tpu.core.supervisor import (
    BACKEND_LOST,
    BackendLost,
    BackendSupervisor,
    FATAL,
    RESOURCE_EXHAUSTED,
    TRANSIENT,
    classify_failure,
)
from shadow_tpu.faults import plan as plan_mod
from shadow_tpu.sim import build_simulation

pytestmark = pytest.mark.quick

DEVICE_YAML = """
general:
  stop_time: 4
  seed: 13
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 bandwidth_down "81920 Kibit" bandwidth_up "81920 Kibit" ]
        edge [ source 0 target 0 latency "50 ms" packet_loss 0.0 ]
      ]
experimental:
  event_capacity: 1024
  events_per_host_per_window: 8
hosts:
  peer:
    quantity: 8
    app_model: phold
    app_options: {msgload: 1, runtime: 3}
"""

ISLANDS_YAML = DEVICE_YAML.replace(
    "  event_capacity: 1024",
    "  event_capacity: 1024\n  num_shards: 2",
)

# two gear tiers so the memory ladder has a smaller pool to retreat to
GEARED_YAML = DEVICE_YAML.replace(
    "  event_capacity: 1024",
    "  event_capacity: 1024\n  pool_gears: 2",
)


def _build(yaml):
    return build_simulation(yaml)


def _run(sim, sync):
    if sync == "optimistic":
        sim.run_optimistic()
    else:
        sim.run()
    return sim


def _quiet_supervisor(policy="wait", **kw):
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("probe_budget_s", 30.0)
    return BackendSupervisor(policy, **kw)


_BASELINES: dict = {}


def _baseline(yaml, sync):
    key = (yaml, sync)
    if key not in _BASELINES:
        sim = _run(_build(yaml), sync)
        _BASELINES[key] = (
            sim.audit_chain(), sim.counters()["events_committed"],
        )
        assert _BASELINES[key][0] != 0
    return _BASELINES[key]


# ---------------------------------------------------------------------------
# classification + typed error + estimator (pure host code)
# ---------------------------------------------------------------------------


def test_classify_resource_exhausted_is_its_own_class():
    assert classify_failure(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to "
                     "allocate 1073741824 bytes")
    ) == RESOURCE_EXHAUSTED
    assert classify_failure(
        RuntimeError("XlaRuntimeError: Resource exhausted: hbm")
    ) == RESOURCE_EXHAUSTED
    assert classify_failure(RuntimeError("failed to allocate request")) \
        == RESOURCE_EXHAUSTED
    assert classify_failure(PoolExhausted("stalled")) == RESOURCE_EXHAUSTED
    # the neighbors keep their classes
    assert classify_failure(RuntimeError("ABORTED: collective")) == TRANSIENT
    assert classify_failure(RuntimeError("UNAVAILABLE: socket closed")) \
        == BACKEND_LOST
    # a chip held by another process fails fast, even where the text
    # also carries an UNAVAILABLE
    assert classify_failure(RuntimeError("device or resource busy")) \
        == FATAL
    assert classify_failure(RuntimeError(
        "Unable to initialize backend 'tpu': UNAVAILABLE: TPU in use")) \
        == FATAL
    assert classify_failure(ValueError("shape mismatch")) == FATAL


def test_pool_exhausted_carries_diagnostics():
    e = PoolExhausted("stalled", window=123, occupancy=900, capacity=1024)
    assert isinstance(e, RuntimeError)
    assert (e.window, e.occupancy, e.capacity) == (123, 900, 1024)


def test_plan_pressure_ops_validate():
    good = {
        "kind": plan_mod.PLAN_KIND,
        "schema_version": plan_mod.PLAN_SCHEMA_VERSION,
        "faults": [
            {"at": "1 s", "op": "exhaust_backend"},
            {"at": "1 s", "op": "exhaust_backend", "recover_after": 3},
            {"at": "2 s", "op": "saturate_pool", "frac": 0.25},
            {"at": "2 s", "op": "saturate_pool"},
        ],
    }
    plan_mod.validate_fault_plan_doc(good)
    faults = plan_mod.parse_fault_plan(good["faults"])
    assert faults[1].recover_after == 3
    assert faults[2].frac == 0.25
    assert faults[3].frac == 0.5  # default
    assert "exhaust_backend" in plan_mod.BACKEND_OPS
    assert "saturate_pool" in plan_mod.DEVICE_OPS
    for bad in (
        [{"at": 1, "op": "saturate_pool", "frac": 0.0}],
        [{"at": 1, "op": "saturate_pool", "frac": 1.5}],
        [{"at": 1, "op": "saturate_pool", "frac": "nope"}],
        [{"at": 1, "op": "exhaust_backend", "recover_after": -1}],
        [{"at": 1, "op": "exhaust_backend", "frac": 0.5}],
    ):
        with pytest.raises(plan_mod.FaultPlanError):
            plan_mod.parse_fault_plan(bad)
    # daemon-level chaos plans may carry pressure ops; device-host ops no
    plan_mod.check_backend_ops(plan_mod.parse_fault_plan(
        [{"at": 1, "op": "exhaust_backend"},
         {"at": 1, "op": "saturate_pool", "frac": 0.5}]
    ))
    with pytest.raises(plan_mod.FaultPlanError):
        plan_mod.check_backend_ops(plan_mod.parse_fault_plan(
            [{"at": 1, "op": "kill_host", "host": 0}]
        ))


def test_hbm_estimator_scales_with_gear_and_budget_env(monkeypatch):
    sim = _build(GEARED_YAML)
    est_top = pressure_mod.estimate_hbm_bytes(sim, level=1)
    est_low = pressure_mod.estimate_hbm_bytes(sim, level=0)
    assert est_top["total_bytes"] > est_low["total_bytes"] > 0
    assert est_top["state_bytes"] == pressure_mod.tree_bytes(sim.state)
    monkeypatch.setenv("SHADOW_TPU_HBM_BUDGET", "1000000000")
    assert pressure_mod.device_memory_budget() == 1_000_000_000
    hb = pressure_mod.headroom_bytes(est_top["total_bytes"])
    assert hb == 1_000_000_000 - est_top["total_bytes"]


def test_supervisor_exhaust_runs_ladder_then_succeeds():
    sup = _quiet_supervisor("abort")
    steps = []

    class Sim:
        def _pressure_ladder_step(self, label):
            steps.append(label)
            return True

        def _drain_to_checkpoint(self, reason, ckpt_dir=None):
            return None

    sup.bind(Sim())
    sup.inject_exhaust(2)
    assert sup.call("run_to", lambda: "ok") == "ok"
    assert len(steps) == 2
    assert sup.counters["exhaustions"] == 2
    assert sup.counters["pressure_steps"] == 2
    assert sup.counters["backend_losses"] == 0


def test_supervisor_exhaust_ladder_exhausted_drains_to_policy():
    sup = _quiet_supervisor("abort")
    drains = []

    class Sim:
        def _pressure_ladder_step(self, label):
            return False  # ladder gave up

        def _drain_to_checkpoint(self, reason, ckpt_dir=None):
            drains.append(reason)
            return None

    sup.bind(Sim())
    sup.inject_exhaust(1)
    with pytest.raises(BackendLost):
        sup.call("run_to", lambda: "ok")
    assert drains and sup.counters["drains"] == 1


def test_controller_saturation_yields_and_relaxes():
    pc = PressureController()
    pc.saturate(0.25)
    assert pc.scaled_marks(800, 600) == (200, 150)

    class Sim:
        def _pressure_relieve_pool(self, step):
            return None  # no rung available: only the yield applies

    assert pc.on_pool_exhausted(Sim(), window=0)
    assert pc.saturate_frac == 0.5
    assert pc.on_pool_exhausted(Sim(), window=0)
    assert pc.saturate_frac == 1.0
    assert not pc.on_pool_exhausted(Sim(), window=0)  # fully yielded
    assert pc.counters["gave_up"] == 1
    # relaxation hysteresis: fill_shrink decays after clean dispatches
    pc.fill_shrink = 2
    for _ in range(pc.policy.recover_after_dispatches):
        pc.note_progress()
    assert pc.fill_shrink == 1


def test_disabled_policy_raises_typed_pool_exhausted(tmp_path):
    """The pre-ladder behavior, typed: with the ladder disabled a
    saturation stall surfaces as PoolExhausted (never a bare
    RuntimeError), after draining the frontier to the checkpoint ring."""
    sim = _build(DEVICE_YAML)
    sim.checkpoint_dir = str(tmp_path)
    ctl = PressureController(PressurePolicy(enabled=False))
    sim.attach_pressure(ctl)
    # saturation so severe the spill tier cannot place a window's inflow
    ctl.saturate_frac = 0.001
    sim._force_spill = True
    with pytest.raises(PoolExhausted) as e:
        sim.run()
    assert e.value.capacity == 1024
    assert e.value.occupancy is not None
    assert ctl.counters["gave_up"] >= 1
    entries = [n for n in os.listdir(tmp_path) if n.startswith("drain-")]
    assert len(entries) == 1  # drained before raising: resumable


# ---------------------------------------------------------------------------
# chaos matrix: exhaust_backend / saturate_pool ×
# {conservative, optimistic} × {global, islands} (fleet below)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sync", ["conservative", "optimistic"])
@pytest.mark.parametrize(
    "yaml", [DEVICE_YAML, ISLANDS_YAML], ids=["global", "islands"]
)
def test_exhaust_backend_ladder_chain_identical(yaml, sync):
    """Acceptance gate: a mid-run RESOURCE_EXHAUSTED drives the ladder
    and the run COMPLETES in-process with the uninterrupted chain."""
    chain, events = _baseline(yaml, sync)
    sim = _build(yaml)
    sim.attach_supervisor(_quiet_supervisor("wait"))
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "exhaust_backend", "recover_after": 2}]
    ))
    _run(sim, sync)
    assert sim.audit_chain() == chain
    assert sim.counters()["events_committed"] == events
    ps = sim.pressure_stats()
    assert ps["backend_exhausted"] == 2
    assert ps["ladder_steps"] == 2
    assert sim.supervisor.counters["exhaustions"] == 2
    assert sim.supervisor.counters["backend_losses"] == 0


@pytest.mark.parametrize(
    "yaml", [DEVICE_YAML, ISLANDS_YAML], ids=["global", "islands"]
)
def test_saturate_pool_spill_ladder_chain_identical(yaml):
    """Sustained simulated pool pressure is absorbed by the spill tier;
    events, order and chain stay bit-identical to the unsaturated run."""
    chain, events = _baseline(yaml, "conservative")
    sim = _build(yaml)
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "saturate_pool", "frac": 0.2}]
    ))
    sim.run()
    assert sim.audit_chain() == chain
    assert sim.counters()["events_committed"] == events
    assert sim.pressure_stats()["saturations"] == 1
    assert sim.spill_stats()["spill_episodes"] >= 1


def test_saturate_pool_optimistic_is_benign():
    """saturate_pool under optimistic sync: the spill marks are unused
    by the speculative driver, so the injection records pressure but the
    run is untouched — and bit-identical."""
    chain, events = _baseline(DEVICE_YAML, "optimistic")
    sim = _build(DEVICE_YAML)
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "saturate_pool", "frac": 0.2}]
    ))
    sim.run_optimistic()
    assert sim.audit_chain() == chain
    assert sim.counters()["events_committed"] == events
    assert sim.pressure_stats()["saturations"] == 1


def test_forced_downshift_overrides_red_zone_and_holds(tmp_path):
    """The memory ladder's first rung on a geared build: park overflow
    host-side, downshift one tier, HOLD the gear down (the red-zone
    upshift rule is overridden) — bit-identical completion."""
    chain, events = _baseline(GEARED_YAML, "conservative")
    sim = _build(GEARED_YAML)
    # force the top gear so a smaller tier exists to retreat to
    if sim._gear < len(sim._gear_ladder) - 1:
        sim._shift_gear(len(sim._gear_ladder) - 1)
        sim._gear_shifts = 0
    sim.attach_supervisor(_quiet_supervisor("wait"))
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "exhaust_backend", "recover_after": 1}]
    ))
    sim.run()
    assert sim.audit_chain() == chain
    assert sim.counters()["events_committed"] == events
    assert sim.pressure_stats()["downshifts"] == 1


# ---------------------------------------------------------------------------
# fleet cells: exhaust → lane eviction / saturate → recorded, chains equal
# ---------------------------------------------------------------------------

GML = """\
graph [
  node [ id 0 bandwidth_down "81920 Kibit" bandwidth_up "81920 Kibit" ]
  edge [ source 0 target 0 latency "50 ms" packet_loss 0.0 ]
]
"""


def _fleet_cfg(seed, stop):
    return {
        "general": {"stop_time": stop, "seed": seed},
        "network": {"graph": {"type": "gml", "inline": GML}},
        "experimental": {
            "event_capacity": 1024,
            "events_per_host_per_window": 8,
            "outbox_slots": 8,
            "inbox_slots": 4,
        },
        "hosts": {
            "peer": {
                "quantity": 8,
                "app_model": "phold",
                "app_options": {
                    "msgload": 2, "runtime": 2, "start_time": "100 ms",
                },
            }
        },
    }


def _fleet_jobs(n=3):
    from shadow_tpu.fleet import JobSpec

    stops = ["900 ms", "1.4 s", "1.1 s"]
    return [
        JobSpec(f"job{i}", _fleet_cfg(100 + i, stops[i])) for i in range(n)
    ]


def _fleet_ref_chains():
    from shadow_tpu.fleet import build_fleet

    ref = build_fleet(_fleet_jobs(), lanes=2, windows_per_dispatch=2)
    ref.run()
    return [r["audit"]["chain"] for r in ref.results()]


@pytest.mark.parametrize("op", ["exhaust_backend", "saturate_pool"])
def test_fleet_pressure_chains_identical(op):
    """Fleet cells of the chaos matrix: the injection fires against the
    fleet frontier; every job's harvested chain still equals the
    uninterrupted sweep's (lane eviction re-runs are pure re-executions)."""
    from shadow_tpu.fleet import build_fleet

    ref_chains = _fleet_ref_chains()
    fleet = build_fleet(_fleet_jobs(), lanes=2, windows_per_dispatch=2)
    if op == "exhaust_backend":
        fleet.attach_supervisor(_quiet_supervisor("wait"))
        fault = {"at": "500 ms", "op": op, "recover_after": 1}
    else:
        fault = {"at": "500 ms", "op": op, "frac": 0.5}
    fleet.attach_faults(plan_mod.parse_fault_plan([fault]))
    fleet.run()
    assert fleet.ok(), [r["status"] for r in fleet.results()]
    assert [r["audit"]["chain"] for r in fleet.results()] == ref_chains
    ps = fleet.pressure_stats()
    if op == "exhaust_backend":
        # pool_gears=1: no smaller tier → the ladder evicted a lane
        assert ps["lane_evictions"] >= 1
        assert fleet.sched.jobs_requeued >= 1
    else:
        assert ps["saturations"] == 1


def test_fleet_optimistic_exhaust_chains_identical():
    from shadow_tpu.fleet import build_fleet

    ref = build_fleet(_fleet_jobs(), lanes=2, windows_per_dispatch=2)
    ref.run_optimistic()
    ref_chains = [r["audit"]["chain"] for r in ref.results()]

    fleet = build_fleet(_fleet_jobs(), lanes=2, windows_per_dispatch=2)
    fleet.attach_supervisor(_quiet_supervisor("wait"))
    fleet.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "500 ms", "op": "exhaust_backend", "recover_after": 1}]
    ))
    fleet.run_optimistic()
    assert fleet.ok(), [r["status"] for r in fleet.results()]
    assert [r["audit"]["chain"] for r in fleet.results()] == ref_chains
    # mid-attempt no rung is safe (the snapshot pins lane rows), so the
    # exhaustion rode the supervisor's drain → recovery → retry path
    assert fleet.supervisor.counters["exhaustions"] >= 1
    assert fleet.pressure_stats()["backend_exhausted"] >= 1


# ---------------------------------------------------------------------------
# serve: memory-aware admission (the preflight estimator vs live headroom)
# ---------------------------------------------------------------------------


def _sweep_doc():
    return {
        "sweep": {"name": "t", "lanes": 2,
                  "matrix": {"general.seed": [1, 2]}},
        **_fleet_cfg(1, "900 ms"),
    }


def test_serve_memory_aware_admission(tmp_path, monkeypatch):
    from shadow_tpu.serve.daemon import ServeOptions, ShadowDaemon

    # a 1 kB budget: nothing fits → shed 429 memory_pressure
    monkeypatch.setenv("SHADOW_TPU_HBM_BUDGET", "1024")
    d = ShadowDaemon(ServeOptions(str(tmp_path / "s1")))
    out = d.submit(_sweep_doc())
    assert out["shed"] == "memory_pressure"
    assert out["estimated_bytes"] > 1024
    assert out["retry_after_s"] >= 1
    assert d.counters["memory_sheds"] == 1
    mem = d._memory_view()
    assert mem["budget_bytes"] == 1024
    assert mem["headroom_bytes"] == 1024
    doc = d.metrics_doc()
    assert doc["counters"]["serve.memory_sheds"] == 1
    assert "pressure.headroom_bytes" in doc["gauges"]
    d.journal.close()

    # no budget (CPU backend): the same submission is admitted
    monkeypatch.delenv("SHADOW_TPU_HBM_BUDGET")
    d2 = ShadowDaemon(ServeOptions(str(tmp_path / "s2")))
    out2 = d2.submit(_sweep_doc())
    assert "id" in out2
    assert d2._memory_view()["budget_bytes"] is None
    d2.journal.close()


def test_config_estimator_is_conservative_and_lane_scaled():
    from shadow_tpu.core.config import load_config

    cfg = load_config(_fleet_cfg(1, "900 ms"))
    one = pressure_mod.estimate_config_bytes(cfg, lanes=1)
    four = pressure_mod.estimate_config_bytes(cfg, lanes=4)
    assert four == 4 * one
    # conservative: at least the raw pool bytes
    assert one > 1024 * (8 + 4 * 4)
