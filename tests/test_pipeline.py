"""Pipelined CPU↔TPU handoff (ISSUE 15): chain-equality matrix
pipelined-vs-serial × {conservative, optimistic} × {global, islands,
fleet}, forced-drain barrier points (fault marks, gear shifts,
checkpoint boundaries, pressure rungs mid-flight), the supervisor
issue/await split, and the pipeline.* telemetry plane.

The load-bearing property: the two-slot pipeline changes WHEN dispatches
are enqueued — never what they compute. Every adopted speculative
dispatch is a pure function of exactly the inputs the serial loop would
have passed (core/pipeline.py recompute rule), so every cell of the
matrix must reproduce the serial driver's audit digest chain
bit-for-bit, including runs whose handoffs mutate state (injections,
gear shifts, checkpoint ring writes, pressure ladders).
"""

import json

import numpy as np
import pytest

from _contracts import assert_current_metrics_schema

from shadow_tpu.core import pipeline as pipeline_mod
from shadow_tpu.core import simtime
from shadow_tpu.faults import plan as plan_mod
from shadow_tpu.core.supervisor import (
    BackendLost,
    BackendSupervisor,
    PendingDispatch,
)
from shadow_tpu.fleet import JobSpec, build_fleet
from shadow_tpu.obs import metrics as obs_metrics
from shadow_tpu.sim import build_simulation

NEVER = int(simtime.NEVER)

GML = """\
graph [
  node [ id 0 ]
  node [ id 1 ]
  node [ id 2 ]
  node [ id 3 ]
  edge [ source 0 target 1 latency "40 ms" ]
  edge [ source 1 target 2 latency "55 ms" ]
  edge [ source 2 target 3 latency "70 ms" ]
  edge [ source 3 target 0 latency "85 ms" ]
  edge [ source 0 target 2 latency "60 ms" ]
  edge [ source 1 target 3 latency "75 ms" ]
  edge [ source 0 target 0 latency "45 ms" ]
  edge [ source 1 target 1 latency "50 ms" ]
  edge [ source 2 target 2 latency "65 ms" ]
  edge [ source 3 target 3 latency "80 ms" ]
]
"""


def _cfg(pipelined=True, stop=6, seed=11, hosts_per=2, runtime=None,
         **exp):
    hosts = {}
    for v in range(4):
        hosts[f"h{v}"] = {
            "quantity": hosts_per, "network_node_id": v,
            "app_model": "phold",
            "app_options": {
                "msgload": 1,
                "runtime": (stop - 1) if runtime is None else runtime,
            },
        }
    experimental = {
        "event_capacity": 1024, "events_per_host_per_window": 8,
        "outbox_slots": 8, "inbox_slots": 4,
        "pipelined_dispatch": pipelined,
    }
    experimental.update(exp)
    return {
        "general": {"stop_time": stop, "seed": seed},
        "network": {"graph": {"type": "gml", "inline": GML}},
        "experimental": experimental,
        "hosts": hosts,
    }


def _build(pipelined=True, faults=None, **kw):
    sim = build_simulation(_cfg(pipelined=pipelined, **kw))
    if faults is not None:
        sim.attach_faults(plan_mod.parse_fault_plan(faults))
    return sim


def _chain(sim):
    return sim.audit_chain(), sim.counters()["events_committed"]


@pytest.fixture(scope="module")
def serial_ref():
    """The serial global conservative chain every pipelined cell must
    reproduce bit-for-bit."""
    sim = build_simulation(_cfg(pipelined=False))
    assert sim.pipelined_dispatch is False
    sim.run(windows_per_dispatch=16)
    assert sim.pipeline_stats() == {}  # serial arm: no pipeline plane
    return _chain(sim)


# ---------------------------------------------------------------------------
# chain-equality matrix: pipelined vs serial × {cons, opt} × layouts
# ---------------------------------------------------------------------------


def test_global_conservative_pipelined_matches(serial_ref):
    sim = build_simulation(_cfg())
    assert sim.pipelined_dispatch is True  # on by default
    sim.run(windows_per_dispatch=16)
    assert _chain(sim) == serial_ref
    st = sim.pipeline_stats()
    # a clean fused run issues ahead at (nearly) every boundary and
    # never has to discard or force-drain
    assert st["issued_ahead"] > 0
    assert st["recompute_discards"] == 0
    assert st["forced_drains"] == 0
    assert st["overlap_ns"] > 0


def test_global_stepwise_pipelined_matches(serial_ref):
    sim = build_simulation(_cfg())
    sim.run_stepwise()
    assert _chain(sim) == serial_ref
    assert sim.pipeline_stats()["issued_ahead"] > 0


def test_global_optimistic_pipelined_matches(serial_ref):
    serial = build_simulation(_cfg(pipelined=False))
    serial.run_optimistic()
    assert _chain(serial) == serial_ref
    sim = build_simulation(_cfg())
    sim.run_optimistic()
    assert _chain(sim) == serial_ref
    assert sim.pipeline_stats()["issued_ahead"] > 0


def test_islands_async_pipelined_matches(serial_ref):
    exp = {"num_shards": 2, "exchange_slots": 16}
    serial = build_simulation(_cfg(pipelined=False, **exp))
    serial.run(windows_per_dispatch=16)
    assert _chain(serial) == serial_ref
    sim = build_simulation(_cfg(**exp))
    assert sim._async is True  # the fused async driver is the default
    sim.run(windows_per_dispatch=16)
    assert _chain(sim) == serial_ref
    assert sim.pipeline_stats()["issued_ahead"] > 0


def test_islands_optimistic_pipelined_matches(serial_ref):
    # the islands optimistic driver is host-stepped (not issued ahead)
    # but must stay chain-exact with the knob on
    sim = build_simulation(_cfg(num_shards=2, exchange_slots=16))
    sim.run_optimistic()
    assert _chain(sim) == serial_ref


def _fleet_jobs(pipelined, n=3):
    # runtime is kernel-shaping (handler constant) and must match across
    # jobs; stop_time and seed are data-plane sweep axes
    return [
        JobSpec(f"job{i}", _cfg(pipelined=pipelined, seed=11 + i,
                                stop=4 + i, runtime=3))
        for i in range(n)
    ]


def test_fleet_pipelined_matches_serial_and_solo():
    serial = build_fleet(_fleet_jobs(False), lanes=2)
    assert serial.pipelined_dispatch is False
    serial.run()
    piped = build_fleet(_fleet_jobs(True), lanes=2)
    assert piped.pipelined_dispatch is True  # adopted from template job
    piped.run()
    rows_s = {r["name"]: r for r in serial.results()}
    rows_p = {r["name"]: r for r in piped.results()}
    assert rows_s.keys() == rows_p.keys()
    for name, rs in rows_s.items():
        rp = rows_p[name]
        assert rp["events_committed"] == rs["events_committed"], name
        assert rp["audit"]["chain"] == rs["audit"]["chain"], name
    # solo parity for one job closes the loop to the global engine
    solo = build_simulation(_cfg(seed=12, stop=5, runtime=3))
    solo.run(windows_per_dispatch=16)
    assert rows_p["job1"]["audit"]["chain"] == solo.audit_chain()
    assert piped.pipeline_stats()["issued_ahead"] > 0
    assert serial.pipeline_stats() == {}


# ---------------------------------------------------------------------------
# forced-drain barrier points: state-mutating handoffs stay serial and
# chain-exact
# ---------------------------------------------------------------------------


def test_fault_mark_forces_drain_chain_exact(serial_ref):
    faults = [{"op": "kill_host", "at": "2 s", "host": 5}]
    serial = _build(pipelined=False, faults=faults)
    serial.run(windows_per_dispatch=4)
    piped = _build(faults=faults)
    piped.run(windows_per_dispatch=4)
    assert _chain(piped) == _chain(serial)
    # the injection fired at the same frontier in both arms
    assert piped.fault_counters["hosts_quarantined"] == 1
    assert (piped.fault_counters["events_drained"]
            == serial.fault_counters["events_drained"])
    st = piped.pipeline_stats()
    # every boundary from the quarantine on is a barrier point (the
    # recurring dead-host drain mutates state), so the pipeline must
    # have refused to speculate at least once
    assert st["forced_drains"] > 0


def test_gear_shift_invalidates_speculation_chain_exact():
    exp = {"pool_gears": 3, "event_capacity": 2048}
    serial = build_simulation(_cfg(pipelined=False, **exp))
    serial.run(windows_per_dispatch=4)
    piped = build_simulation(_cfg(**exp))
    piped.run(windows_per_dispatch=4)
    assert _chain(piped) == _chain(serial)


def test_checkpoint_boundary_forces_drain(tmp_path, serial_ref):
    def run(pipelined, sub):
        d = tmp_path / sub
        d.mkdir()
        sim = build_simulation(_cfg(pipelined=pipelined))
        sim.configure_auto_checkpoint(str(d), int(2e9), retain=4)
        sim.run(windows_per_dispatch=16)
        return sim, sorted(p.name for p in d.glob("ckpt-*.npz"))

    serial, rings_s = run(False, "serial")
    piped, rings_p = run(True, "piped")
    assert _chain(piped) == _chain(serial) == serial_ref
    assert rings_p == rings_s and rings_p  # same ring cadence
    assert piped.pipeline_stats()["forced_drains"] > 0


def test_pressure_rung_mid_flight_chain_exact(serial_ref):
    faults = [{"op": "exhaust_backend", "at": "2 s", "recover_after": 1}]
    exp = {"pool_gears": 2, "event_capacity": 2048}
    serial = _build(pipelined=False, faults=faults, **exp)
    serial.run(windows_per_dispatch=8)
    piped = _build(faults=faults, **exp)
    piped.run(windows_per_dispatch=8)
    assert _chain(piped) == _chain(serial)
    assert piped.resilience_stats()["exhaustions"] > 0


def test_kill_backend_on_pipelined_run_drains_and_resumes(tmp_path):
    faults = [{"op": "kill_backend", "at": "2 s", "recover_after": 1}]
    ref = build_simulation(_cfg())
    ref.run(windows_per_dispatch=16)
    sim = _build(faults=faults)
    sim.checkpoint_dir = str(tmp_path)
    sim.attach_supervisor(
        BackendSupervisor(policy="wait", sleep=lambda s: None)
    )
    sim.run(windows_per_dispatch=16)
    assert _chain(sim) == _chain(ref)
    rs = sim.resilience_stats()
    assert rs["backend_losses"] >= 1 and rs["hot_resumes"] >= 1


# ---------------------------------------------------------------------------
# supervisor issue/await split units
# ---------------------------------------------------------------------------


class _FakeSim:
    def __init__(self):
        self.drains = []

    def _drain_to_checkpoint(self, reason, ckpt_dir=None):
        self.drains.append(reason)
        return None

    def _rebind_kernels(self):
        pass


def _fake_sup(**kw):
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("probe_fn", lambda: True)
    sup = BackendSupervisor(**kw)
    sup.bind(_FakeSim())
    return sup


def test_transient_failure_on_issued_ahead_walks_retry_ladder():
    sup = _fake_sup(max_retries=3)
    calls = {"issue": 0, "fetch": 0}

    def issue():
        calls["issue"] += 1
        return "futures"

    def fetch(out):
        assert out == "futures"
        calls["fetch"] += 1
        if calls["fetch"] < 3:
            raise RuntimeError("ABORTED: collective interrupted, retry")
        return "result"

    p = sup.issue("run_to", issue, fetch)
    assert calls["issue"] == 1  # issued ahead, exactly once
    out = sup.await_result(p)
    assert out == "result"
    # retries re-ran BOTH halves (issue re-reads bound kernels)
    assert calls["issue"] == 3
    assert sup.counters["retries"] == 2
    assert sup.counters["dispatches"] == 3


def test_backend_loss_on_issued_ahead_drains_cleanly():
    sup = _fake_sup(policy="abort")

    def fetch(out):
        raise RuntimeError("backend_unavailable: socket closed")

    p = sup.issue("run_to", lambda: "futures", fetch)
    with pytest.raises(BackendLost):
        sup.await_result(p)
    assert sup._sim.drains == ["backend_lost:run_to"]
    assert sup.counters["backend_losses"] == 1


def test_issue_skipped_while_disrupted_then_awaits_clean():
    sup = _fake_sup(policy="wait")
    sup.inject_kill(recover_after=0)
    assert sup.pending_disruption
    calls = {"issue": 0}

    def issue():
        calls["issue"] += 1
        return "f"

    p = sup.issue("run_to", issue, lambda out: out)
    assert calls["issue"] == 0  # launch skipped against the dead backend
    out = sup.await_result(p)  # recovery (hot resume), then fresh issue
    assert out == "f" and calls["issue"] == 1
    assert sup.counters["hot_resumes"] == 1


def test_injected_exhaust_fires_on_awaited_half():
    sup = _fake_sup()
    rungs = []
    sup._sim._pressure_ladder_step = lambda label: (
        rungs.append(label) or True
    )
    sup.inject_exhaust(recover_after=1)
    p = sup.issue("run_to", lambda: "f", lambda out: out)
    assert sup.await_result(p) == "f"
    assert len(rungs) == 1  # one ladder rung per injected failure
    assert sup.counters["exhaustions"] == 1


def test_pending_dispatch_direct_and_abandon():
    p = PendingDispatch.direct("x", lambda: 41, lambda out: out + 1)
    assert p.await_direct() == 42
    # claim is once-only: a second await re-runs the halves
    assert p.await_direct() == 42
    calls = []
    p2 = PendingDispatch.direct("y", lambda: calls.append(1) or 1,
                                lambda out: out)
    p2.abandon()
    assert p2.claim() is None  # abandoned futures are never observed


def test_two_slot_pipeline_recompute_rule():
    stats = pipeline_mod.new_stats()
    pipe = pipeline_mod.TwoSlotPipeline(stats)
    tok = object()
    p = PendingDispatch.direct("z", lambda: 7, lambda out: out)
    pipe.put(p, tok, ("args",))
    # args drift → discard + recompute tally
    assert pipe.take(tok, ("other",)) is None
    assert stats["recompute_discards"] == 1
    p2 = PendingDispatch.direct("z", lambda: 7, lambda out: out)
    pipe.put(p2, tok, ("args",))
    # state drift → invalidate discards
    pipe.invalidate(object())
    assert not pipe.pending and stats["recompute_discards"] == 2
    p3 = PendingDispatch.direct("z", lambda: 7, lambda out: out)
    pipe.put(p3, tok, ("args",))
    assert pipe.take(tok, ("args",)) is p3  # exact match adopts
    assert stats["issued_ahead"] == 3
    assert stats["overlap_ns"] >= 0


# ---------------------------------------------------------------------------
# telemetry: pipeline.* metrics (schema v14) + issue/await/host_drain spans
# ---------------------------------------------------------------------------


def test_pipeline_metrics_schema_v14(tmp_path):
    sim = build_simulation(_cfg())
    sim.run(windows_per_dispatch=16)
    session = obs_metrics.ObsSession()
    session.finalize(sim)
    path = tmp_path / "m.json"
    doc = session.metrics.dump(str(path))
    assert_current_metrics_schema(doc)
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    c = doc["counters"]
    assert c["pipeline.issued_ahead"] > 0
    assert c["pipeline.overlap_ns"] > 0
    assert c["pipeline.forced_drains"] == 0
    assert c["pipeline.recompute_discards"] == 0


def test_serial_run_emits_no_pipeline_keys(tmp_path):
    sim = build_simulation(_cfg(pipelined=False))
    sim.run(windows_per_dispatch=16)
    session = obs_metrics.ObsSession()
    session.finalize(sim)
    doc = session.metrics.dump(str(tmp_path / "m.json"))
    assert not [k for k in doc["counters"] if k.startswith("pipeline.")]
    assert not [k for k in doc["gauges"] if k.startswith("pipeline.")]


def test_trace_spans_and_overlap_efficiency(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    try:
        import trace_summary
    finally:
        sys.path.pop(0)
    from shadow_tpu.obs.trace import ChromeTracer

    def run(pipelined, name):
        sim = build_simulation(_cfg(pipelined=pipelined))
        tracer = ChromeTracer()
        sim.obs_session = obs_metrics.ObsSession(tracer=tracer)
        sim.run(windows_per_dispatch=16)
        path = tmp_path / name
        tracer.write(str(path))
        with open(path) as f:
            return json.load(f)

    doc = run(True, "piped.json")
    names = {e.get("name") for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    assert {"issue", "await", "host_drain"} <= names
    ov = trace_summary.overlap_stats(doc)
    assert ov is not None and ov["issued_ahead"] > 0
    assert ov["adopted"] > 0
    assert 0.0 <= ov["overlap_efficiency"] <= 1.0
    # the aggregate summary still reads the span rows
    rows, _ = trace_summary.summarize(doc)
    assert any(r["name"] == "issue" for r in rows)

    serial = run(False, "serial.json")
    snames = {e.get("name") for e in serial["traceEvents"]
              if e.get("ph") == "X"}
    assert "issue" not in snames and "await" not in snames
    assert trace_summary.overlap_stats(serial) is None


def test_fetch_half_splits_into_wait_and_fetch_spans():
    from shadow_tpu.obs.trace import ChromeTracer

    sim = build_simulation(_cfg())
    tracer = ChromeTracer()
    sim.obs_session = obs_metrics.ObsSession(tracer=tracer)
    sim.run(windows_per_dispatch=16)
    spans = [e for e in tracer.to_doc()["traceEvents"] if e.get("ph") == "X"]

    def parent(e):
        for p in spans:
            if (p["name"] in ("await", "dispatch")
                    and p["ts"] <= e["ts"]
                    and e["ts"] + e["dur"] <= p["ts"] + p["dur"]):
                return p["name"]
        return None

    kids = [e for e in spans if e["name"] in ("wait", "fetch")]
    assert {e["name"] for e in kids} == {"wait", "fetch"}
    # one wait and one fetch per awaited dispatch, each inside its parent
    assert all(parent(e) for e in kids)
    n_await = sum(e["name"] == "await" for e in spans)
    assert n_await > 0
    assert sum(parent(e) == "await" for e in kids) == 2 * n_await


def test_session_reads_counters_fetched_with_the_dispatch():
    from shadow_tpu.obs.prof import ProfRecorder

    sim = build_simulation(_cfg())
    prof = ProfRecorder()
    session = obs_metrics.ObsSession(prof=prof)
    sim.obs_session = session
    device_read = sim.counters

    def no_read():
        raise AssertionError("a device read at the dispatch boundary")

    sim.counters = no_read
    sim.run(windows_per_dispatch=16)
    sim.counters = device_read
    assert sim.boundary_counters is None
    total = sim.counters()["events_committed"]
    assert total > 0
    # the boundaries' host copies add up to the committed totals
    assert session._last_events == total
    assert sum(r["d_events"] for r in prof.intervals()) == total
    h = session.metrics.histogram("round.events_per_sec")
    assert h.count > 0 and h.min > 0


def test_handoff_hook_runs_and_mutation_discards_speculation():
    seen = []

    sim = build_simulation(_cfg())
    sim.add_handoff_hook(lambda s, mn: seen.append(mn))
    sim.run(windows_per_dispatch=16)
    assert seen and all(isinstance(x, int) for x in seen)
    ref = build_simulation(_cfg(pipelined=False))
    ref.run(windows_per_dispatch=16)
    assert _chain(sim) == _chain(ref)

    # a state-mutating hook triggers the recompute rule, chains intact
    def mutate(s, mn):
        s.state = s.state.replace(now=s.state.now + 0)

    sim2 = build_simulation(_cfg())
    sim2.add_handoff_hook(mutate)
    sim2.run(windows_per_dispatch=16)
    assert _chain(sim2) == _chain(ref)
    st = sim2.pipeline_stats()
    assert st["recompute_discards"] > 0
