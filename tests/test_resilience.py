"""Survivable execution (ISSUE 6): backend-loss supervision, drain-to-
checkpoint, and audit-verified resume.

The acceptance gate: a `kill_backend` injection mid-run drains to a
crash-consistent checkpoint, and the resumed (or CPU-failover) run's
final audit digest chain is BIT-IDENTICAL to an uninterrupted run —
across {conservative, optimistic} × {global, islands, fleet}. The chain
(obs/audit.py, PR 5) is the proof instrument: recovery that merely
"looks right" cannot pass it.

Supervisors here inject a no-op sleep and tiny probe budgets: wall-clock
scheduling is the only thing perturbed — simulation results never depend
on it, which is exactly the property under test.
"""

import json
import os

import pytest

from _contracts import assert_current_metrics_schema

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
from shadow_tpu.obs import audit as audit_mod
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


def _build(yaml):
    return build_simulation(yaml)


def _run(sim, sync):
    if sync == "optimistic":
        sim.run_optimistic()
    else:
        sim.run()
    return sim


def _quiet_supervisor(policy, **kw):
    """A supervisor whose waits are instantaneous: wall scheduling only —
    never simulation results."""
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("probe_budget_s", 30.0)
    return BackendSupervisor(policy, **kw)


_BASELINES: dict = {}


def _baseline(yaml, sync):
    """One uninterrupted run per (layout, sync): (chain, events)."""
    key = (yaml, sync)
    if key not in _BASELINES:
        sim = _run(_build(yaml), sync)
        _BASELINES[key] = (
            sim.audit_chain(), sim.counters()["events_committed"],
        )
        assert _BASELINES[key][0] != 0
    return _BASELINES[key]


# ---------------------------------------------------------------------------
# classification + supervisor unit behavior (pure host code)
# ---------------------------------------------------------------------------


def test_classify_failure():
    assert classify_failure(RuntimeError("UNAVAILABLE: socket closed")) \
        == BACKEND_LOST
    assert classify_failure(RuntimeError("connection reset by peer")) \
        == BACKEND_LOST
    assert classify_failure(BackendLost("x")) == BACKEND_LOST
    # schema-v8 pressure plane: XLA OOM is its own class now — the
    # degradation ladder handles it, not a blind retry (PR 9)
    assert classify_failure(RuntimeError("RESOURCE_EXHAUSTED: hbm")) \
        == RESOURCE_EXHAUSTED
    assert classify_failure(RuntimeError("ABORTED: collective")) \
        == TRANSIENT
    assert classify_failure(ValueError("shape mismatch")) == FATAL
    assert classify_failure(RuntimeError("speculation violation")) == FATAL


@pytest.mark.parametrize("msg", [
    # mesh-collective runtime failures: ONE participant chip died.
    # These must classify BACKEND_LOST (drain + policy) — never
    # TRANSIENT (a bounded retry against a dead ppermute peer spins
    # until the retry budget burns) and never FATAL (it is an
    # infrastructure failure, not a bug) — even when the runtime
    # phrases them with a transient-sounding prefix.
    "ABORTED: ppermute participant failed on device 3",
    "INTERNAL: collective-permute peer unreachable",
    "ABORTED: all-reduce timed out waiting for peer",
    "all_gather failed: remote device lost contact",
    "collective operation aborted: participant failed",
    "NCCL error: peer failure detected",
    "ICI link down between chips 2 and 3",
])
def test_mesh_collective_failures_classify_chip_scoped(msg):
    """ISSUE 13 satellite: the chip-scoped marker table, mirroring the
    backend-lost marker rows above — BACKEND_LOST and chip-scoped."""
    from shadow_tpu.core.supervisor import chip_scoped

    exc = RuntimeError(msg)
    assert classify_failure(exc) == BACKEND_LOST
    assert chip_scoped(exc)


def test_generic_transient_stays_transient():
    """The chip table must not swallow the generic retry class: a bare
    'ABORTED: collective' (no op-scoped marker) keeps its bounded
    retry, and plain transients are untouched."""
    from shadow_tpu.core.supervisor import chip_scoped

    assert classify_failure(RuntimeError("ABORTED: collective")) \
        == TRANSIENT
    assert classify_failure(RuntimeError("try again later")) == TRANSIENT
    assert not chip_scoped(RuntimeError("try again later"))


def test_supervisor_transient_retry_then_success():
    sup = _quiet_supervisor("abort")
    calls = {"n": 0}

    def thunk():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("ABORTED: collective interrupted")
        return "ok"

    assert sup.call("step", thunk) == "ok"
    assert calls["n"] == 3
    assert sup.counters["retries"] == 2
    assert sup.counters["backoffs"] == 2


def test_supervisor_transient_exhaustion_escalates_to_loss():
    sup = _quiet_supervisor("abort", max_retries=1)

    class Sim:
        def _drain_to_checkpoint(self, reason, ckpt_dir=None):
            return None

    sup.bind(Sim())
    with pytest.raises(BackendLost):
        sup.call("step", lambda: (_ for _ in ()).throw(
            RuntimeError("ABORTED: again and again")
        ))
    assert sup.counters["backend_losses"] == 1
    assert sup.counters["drains"] == 1


def test_supervisor_fatal_propagates_unchanged():
    sup = _quiet_supervisor("wait")
    with pytest.raises(ValueError, match="real bug"):
        sup.call("step", lambda: (_ for _ in ()).throw(
            ValueError("real bug")
        ))
    assert sup.counters["drains"] == 0


def test_plan_backend_ops_validate():
    good = {
        "kind": plan_mod.PLAN_KIND,
        "schema_version": plan_mod.PLAN_SCHEMA_VERSION,
        "faults": [
            {"at": "1 s", "op": "kill_backend"},
            {"at": "1 s", "op": "kill_backend", "recover_after": 2},
            {"at": "2 s", "op": "stall_backend", "count": 3},
        ],
    }
    plan_mod.validate_fault_plan_doc(good)
    faults = plan_mod.parse_fault_plan(good["faults"])
    assert faults[1].recover_after == 2
    assert all(f.op in plan_mod.BACKEND_OPS for f in faults)
    for bad in (
        [{"at": 1, "op": "kill_backend", "recover_after": -1}],
        [{"at": 1, "op": "kill_backend", "host": 3}],
        [{"at": 1, "op": "stall_backend", "count": 0}],
    ):
        with pytest.raises(plan_mod.FaultPlanError):
            plan_mod.parse_fault_plan(bad)


# ---------------------------------------------------------------------------
# chaos matrix: kill_backend mid-run, drain → resume, across
# {conservative, optimistic} × {global, islands}; fleet below
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sync", ["conservative", "optimistic"])
@pytest.mark.parametrize(
    "yaml", [DEVICE_YAML, ISLANDS_YAML], ids=["global", "islands"]
)
def test_kill_backend_drain_resume_chain_identical(yaml, sync, tmp_path):
    """Acceptance gate: drain at the injected loss, resume from the drain
    checkpoint, finish — the final digest chain and committed-event total
    are bit-identical to the uninterrupted run's."""
    chain, events = _baseline(yaml, sync)

    sim = _build(yaml)
    sim.checkpoint_dir = str(tmp_path)
    sim.attach_supervisor(_quiet_supervisor("abort"))
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend"}]  # stays down: abort path
    ))
    with pytest.raises(BackendLost, match="drained to"):
        _run(sim, sync)
    entries = [n for n in os.listdir(tmp_path) if n.startswith("drain-")]
    assert len(entries) == 1
    # drain metadata rides the checkpoint header (core/checkpoint.py)
    from shadow_tpu.core import checkpoint as ckpt_mod

    meta = ckpt_mod.load_meta(str(tmp_path / entries[0]))
    assert meta["drain"]["reason"].startswith("backend_lost:")
    assert meta["drain"]["policy"] == "abort"
    assert "chain" in meta["audit"]

    resumed = _build(yaml)
    info = resumed.resume_from(str(tmp_path))
    assert info["fallbacks"] == 0
    _run(resumed, sync)
    assert resumed.audit_chain() == chain
    assert resumed.counters()["events_committed"] == events


@pytest.mark.parametrize(
    "yaml", [DEVICE_YAML, ISLANDS_YAML], ids=["global", "islands"]
)
def test_kill_backend_cpu_failover_chain_identical(yaml):
    """--on-backend-loss cpu: the run completes in-process on the CPU
    backend with the exact uninterrupted chain; the supervisor records
    the failover (and the failback once the primary answers again)."""
    chain, events = _baseline(yaml, "conservative")
    sim = _build(yaml)
    sup = _quiet_supervisor("cpu", recheck_every=1)
    sim.attach_supervisor(sup)
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend", "recover_after": 1}]
    ))
    # short dispatches: several post-failover rechecks, so the primary's
    # simulated recovery (second probe) triggers the upshift back
    sim.run(windows_per_dispatch=4)
    assert sim.audit_chain() == chain
    assert sim.counters()["events_committed"] == events
    assert sup.counters["drains"] == 1
    assert sup.counters["failovers"] == 1
    assert sup.counters["failbacks"] == 1
    assert not sup.failover  # ended back on the primary


def test_kill_backend_wait_hot_resume():
    """--on-backend-loss wait: re-probe until the simulated backend
    answers, rebind kernels, continue — nothing lost, chain identical."""
    chain, events = _baseline(DEVICE_YAML, "conservative")
    sim = _build(DEVICE_YAML)
    sup = _quiet_supervisor("wait")
    sim.attach_supervisor(sup)
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend", "recover_after": 3}]
    ))
    sim.run()
    assert sim.audit_chain() == chain
    assert sim.counters()["events_committed"] == events
    assert sup.counters["hot_resumes"] == 1
    assert sup.counters["probes"] >= 3
    assert sup.counters["downtime_ns"] >= 0


def test_wait_budget_exhaustion_still_drains(tmp_path):
    """A backend that never returns exhausts the probe budget: the run
    dies with BackendLost, but the drain checkpoint is already on disk."""
    sim = _build(DEVICE_YAML)
    sim.checkpoint_dir = str(tmp_path)
    sup = _quiet_supervisor("wait", probe_budget_s=0.0)
    sim.attach_supervisor(sup)
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend"}]
    ))
    with pytest.raises(BackendLost, match="probe budget"):
        sim.run()
    assert any(n.startswith("drain-") for n in os.listdir(tmp_path))


def test_stall_backend_escalation_ladder():
    """stall_backend: consecutive deadline misses escalate to a probe
    (the bounded-lag signal); a healthy probe keeps the run going and the
    result is untouched."""
    chain, events = _baseline(DEVICE_YAML, "conservative")
    sim = _build(DEVICE_YAML)
    sup = _quiet_supervisor("wait", stall_limit=2)
    sim.attach_supervisor(sup)
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "stall_backend", "count": 2}]
    ))
    sim.run(windows_per_dispatch=4)
    assert sim.audit_chain() == chain
    assert sup.counters["stalls"] == 2
    assert sup.counters["probes"] >= 1
    assert sup.counters["drains"] == 0  # healthy probe: no escalation


def test_resume_skips_already_fired_backend_faults(tmp_path):
    """Re-attaching the SAME fault plan on resume must not re-drain: the
    outage at/before the restored frontier already happened — it is the
    reason the run is resuming."""
    chain, events = _baseline(DEVICE_YAML, "conservative")
    sim = _build(DEVICE_YAML)
    sim.checkpoint_dir = str(tmp_path)
    sim.attach_supervisor(_quiet_supervisor("abort"))
    plan = [{"at": "1 s", "op": "kill_backend"}]
    sim.attach_faults(plan_mod.parse_fault_plan(plan))
    with pytest.raises(BackendLost):
        sim.run()

    resumed = _build(DEVICE_YAML)
    resumed.attach_faults(plan_mod.parse_fault_plan(plan))  # re-attached
    resumed.attach_supervisor(_quiet_supervisor("abort"))
    resumed.resume_from(str(tmp_path))
    assert resumed.fault_injector.pending == 0  # marked fired on resume
    resumed.run()
    assert resumed.audit_chain() == chain
    assert resumed.counters()["events_committed"] == events


def test_digest_doc_diff_confirms_resume_parity(tmp_path):
    """The divergence bisector view of the gate: digest DOCUMENTS from an
    uninterrupted run and a drained+resumed run end on the same final
    chain and per-host sub-chains (frontier-aligned diff, the engine
    behind tools/diff_digest.py)."""
    ref = _build(DEVICE_YAML)
    ref.attach_audit(meta={"arm": "ref"})
    ref.run()
    doc_ref = ref.write_digest(str(tmp_path / "ref.json"))

    sim = _build(DEVICE_YAML)
    sim.checkpoint_dir = str(tmp_path / "ck")
    sim.attach_supervisor(_quiet_supervisor("abort"))
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend"}]
    ))
    with pytest.raises(BackendLost):
        sim.run()
    resumed = _build(DEVICE_YAML)
    resumed.attach_audit(meta={"arm": "resumed"})
    resumed.resume_from(str(tmp_path / "ck"))
    resumed.run()
    doc_res = resumed.write_digest(str(tmp_path / "resumed.json"))

    rep = audit_mod.diff_digest_docs(doc_ref, doc_res)
    assert rep["final_chain_equal"]
    assert rep["divergent_hosts"] == []
    assert rep["first_divergent_record"] is None


# ---------------------------------------------------------------------------
# fleet: whole-sweep drain, admission pause, requeue, resume; lane reclaim
# ---------------------------------------------------------------------------


def _job_cfg(seed, stop_s, quantity=8):
    return {
        "general": {"stop_time": f"{stop_s} s", "seed": seed},
        "network": {"graph": {"type": "gml", "inline": (
            'graph [\n'
            '  node [ id 0 bandwidth_down "81920 Kibit" '
            'bandwidth_up "81920 Kibit" ]\n'
            '  edge [ source 0 target 0 latency "50 ms" '
            'packet_loss 0.0 ]\n'
            ']\n')}},
        "experimental": {"event_capacity": 512,
                         "events_per_host_per_window": 8,
                         "outbox_slots": 8, "inbox_slots": 4},
        "hosts": {"peer": {"quantity": quantity, "app_model": "phold",
                           "app_options": {"msgload": 1, "runtime": 1}}},
    }


@pytest.fixture(scope="module")
def fleet_cfgs():
    return [_job_cfg(100 + i, 2 + i) for i in range(3)]


@pytest.fixture(scope="module")
def fleet_solo_chains(fleet_cfgs):
    chains = []
    for c in fleet_cfgs:
        s = build_simulation(c)
        s.run()
        chains.append(s.audit_chain())
    return chains


@pytest.mark.parametrize("sync", ["conservative", "optimistic"])
def test_fleet_kill_backend_drain_and_resume(
    fleet_cfgs, fleet_solo_chains, sync, tmp_path
):
    """Fleet acceptance leg: kill_backend mid-sweep drains every running
    lane's slice + a drain-annotated manifest, pauses admission, requeues
    the in-flight jobs, and `resume_fleet` finishes the sweep with every
    job's chain equal to its solo run."""
    from shadow_tpu.fleet import JobSpec, build_fleet, resume_fleet

    fleet = build_fleet(
        [JobSpec(name=f"j{i}", config=fleet_cfgs[i]) for i in range(3)],
        lanes=2, checkpoint_dir=str(tmp_path),
    )
    fleet.attach_supervisor(_quiet_supervisor("abort"))
    fleet.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend"}]
    ))
    with pytest.raises(BackendLost):
        if sync == "optimistic":
            fleet.run_optimistic()
        else:
            fleet.run()
    # drain truth: admission paused, in-flight lanes requeued in-memory,
    # manifest carries the drain reason with the slices still RUNNING
    assert fleet._admission_paused
    assert fleet.sched.jobs_requeued >= 1
    assert all(r.status == "queued" for r in fleet.sched.records)
    man = json.load(open(tmp_path / "manifest.json"))
    assert man["drain"]["reason"].startswith("backend_lost:")
    running = [e for e in man["jobs"] if e["status"] == "running"]
    assert running and all("file" in e for e in running)

    resumed = resume_fleet(str(tmp_path))
    if sync == "optimistic":
        resumed.run_optimistic()
    else:
        resumed.run()
    assert resumed.ok()
    by_name = {r.name: r.audit.get("chain") for r in resumed.sched.records}
    for i in range(3):
        assert by_name[f"j{i}"] == fleet_solo_chains[i], f"j{i}"


def test_fleet_kill_backend_wait_recovers_in_process(
    fleet_cfgs, fleet_solo_chains
):
    """Fleet + policy wait: the sweep survives the outage in-process —
    admission resumes after recovery and every chain matches solo."""
    from shadow_tpu.fleet import JobSpec, build_fleet

    fleet = build_fleet(
        [JobSpec(name=f"j{i}", config=fleet_cfgs[i]) for i in range(3)],
        lanes=2,
    )
    sup = _quiet_supervisor("wait")
    fleet.attach_supervisor(sup)
    fleet.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend", "recover_after": 2}]
    ))
    fleet.run()
    assert fleet.ok()
    assert not fleet._admission_paused
    assert sup.counters["drains"] == 1
    assert sup.counters["hot_resumes"] == 1
    by_name = {r.name: r.audit.get("chain") for r in fleet.sched.records}
    for i in range(3):
        assert by_name[f"j{i}"] == fleet_solo_chains[i], f"j{i}"


def test_fleet_deadline_kill_reclaims_lane_immediately(fleet_cfgs):
    """Satellite gate: a job killed at its wall-clock deadline frees its
    lane for the admission queue in the same pass (lane_reclaims), and
    the queued job still completes."""
    from shadow_tpu.fleet import JobSpec, build_fleet
    from shadow_tpu.obs import metrics as obs_metrics

    jobs = [
        JobSpec(name="doomed", config=_job_cfg(7, 30), deadline_s=1e-6),
        JobSpec(name="healthy", config=_job_cfg(8, 2)),
    ]
    fleet = build_fleet(jobs, lanes=1)
    fleet.run()
    rec = {r.name: r for r in fleet.sched.records}
    assert rec["doomed"].status == "timeout"
    assert rec["healthy"].status == "done"
    assert fleet.sched.lane_reclaims >= 1
    # resilience.lane_reclaims rides the fleet metrics doc (schema v6)
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.snapshot_fleet(fleet, reg)
    doc = reg.to_doc()
    obs_metrics.validate_metrics_doc(doc)
    assert doc["counters"]["resilience.lane_reclaims"] >= 1


def test_metrics_schema_v6_resilience_namespace():
    """snapshot_device emits the resilience.* namespace from the attached
    supervisor, and the v6 validator accepts it (and rejects negatives)."""
    from shadow_tpu.obs import metrics as obs_metrics

    sim = _build(DEVICE_YAML)
    sup = _quiet_supervisor("cpu", recheck_every=1)
    sim.attach_supervisor(sup)
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend", "recover_after": 1}]
    ))
    sim.run()
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.snapshot_device(sim, reg)
    doc = reg.to_doc()
    assert_current_metrics_schema(doc)
    obs_metrics.validate_metrics_doc(doc)
    assert doc["counters"]["resilience.drains"] == 1
    assert doc["counters"]["resilience.failovers"] == 1
    bad = dict(doc)
    bad["counters"] = {**doc["counters"], "resilience.drains": -1}
    with pytest.raises(ValueError, match="resilience"):
        obs_metrics.validate_metrics_doc(bad)
