"""Host-side metrics registry: counters / gauges / histograms, snapshotted
at CPU<->TPU handoff boundaries and dumped as versioned JSON.

Namespaces in the dumped document:
  engine.*  the engine Counters struct (core/state.py), fetched once
  obs.*     the device counter block (obs/counters.py): window plane,
            per-host event totals, virtual-time roughness
  net.*     device network-plane counters read from SimState subs
            (nic tx/rx, router CoDel drops, TCP retransmits/timeouts)
  wall.*    driver wall-time histograms (compile/dispatch/host phases)
  round.*   per-dispatch-round throughput series

The JSON schema (docs/observability.md) carries `schema_version`;
`validate_metrics_doc` is the reference validator used by the tier-1
smoke test and available to downstream consumers.
"""

from __future__ import annotations

import json
import time
from contextlib import contextmanager, nullcontext

import numpy as np

from shadow_tpu.obs import counters as obs_counters

# v18: prof.* profiling plane (obs/prof.py + obs/hist.py): mergeable
# log-bucketed latency histograms surfaced as p50/p90/p99/count gauges
# (dispatch_wall_ns / host_drain_wall_ns / window_width_ns, plus the
# serve plane's request_ns), the interval-ring posture counters
# (prof.intervals / prof.dropped), and the critical-path attribution
# gauges prof.critical_shard / prof.blocked_frac / prof.wall_frac;
# v17: qdisc.* per-interface scheduling plane (net/qdisc/):
# enqueues/dequeues plus the split drop tallies (drops_overflow /
# drops_red / drops_codel) for the PIFO and Eiffel-bucketed
# disciplines, and depth_max/depth_mean/sojourn_mean_ns occupancy
# gauges over the [H, Q] queue plane;
# v16: federation.* federated serve plane (serve/federation.py +
# serve/router.py): placements/steals/failovers/replayed_sweeps/
# probes/peers_lost/handoff_recoveries counters for the N-daemon
# router, plus peers_up/peers_total/peers_suspect membership gauges
# and the queue_depth_max/min spread the work stealer flattens;
# v15: hostplane.* multi-worker host plane (core/hostplane.py): worker
# pool width, sharded-drain count, canonical-merge wall, per-worker
# drain wall (drain_ns_w<i>), serial-fallback re-runs after a worker
# exception, and placement-derived host->worker re-pins;
# v14: pipeline.* pipelined-handoff namespace (core/pipeline.py + the
# driver loops: issued-ahead dispatch count, overlap_ns of host-drain
# time hidden behind in-flight device work, forced_drains at
# state-mutating barrier points, recompute_discards where a drained
# handoff invalidated a speculative issue);
# v13: dropped the never-emitted `bench` namespace from the closed
# table — the contract auditor (analysis/contracts.py SLC002) requires
# every registered namespace to have a statically-visible emitter, and
# no gate ever wrote a bench.* key;
# v12: elastic mesh resilience (parallel/elastic.py): mesh.chips_up/
# chips_total posture gauges, mesh.chips_lost/relayouts/re_expansions/
# relayout_downtime_ns/kernel_rebuilds/reexpand_holds counters for the
# drain → relayout → re-expand loop, and resilience.chip_losses (the
# chip-scoped subset of backend_losses, core/supervisor.py);
# v11: mesh.* multi-chip namespace (parallel/{mesh,islands}.py: per-chip
# committed-event balance, neighbor-only frontier-exchange collective
# volume + partner counts, placement cut-cost gauges, and exchange-
# schedule rebuild counters for the shard_map mesh execution plane);
# v10: balance.* self-balancing-fleet namespace (parallel/balancer.py:
# verified live migrations / rollbacks / interlock holds plus controller
# posture gauges, and the fleet scheduler's load-packing + lane-steal
# counters); v9: async.* asynchronous-conservative-sync namespace
# (parallel/islands.py + parallel/lookahead.py: superstep/shard-window/
# yield/blocked-on-neighbor counters plus frontier spread, spread-bound
# and lookahead gauges); v8: pressure.* resource-pressure namespace
# (core/pressure.py: degradation-ladder rungs — downshifts/upshifts/
# spill escalations/lane evictions/job sheds — plus HBM estimate +
# headroom gauges and memory-shed admission counters on the serve
# plane); v7: serve.*
# sim-as-a-service namespace (shadow_tpu/serve: journal records/replays,
# admission sheds, kernel-cache hits/misses/evictions, drains); v6:
# resilience.* backend-supervision namespace (core/supervisor.py:
# retries, backoffs, stalls, drains, failovers, downtime_ns, fleet lane
# reclaims); v5: audit.* determinism-audit namespace (digest chain,
# obs/audit.py) + optional per-job `audit` sub-object on fleet.jobs[*]
# rows; v4: optional top-level `fleet` section (fleet.jobs[*] per-job
# rows) + fleet.* counters; v3: faults.* recovery counters
SCHEMA_VERSION = 18
DOC_KIND = "shadow_tpu.metrics"

# metrics-doc `fleet.jobs[*]` rows must carry at least these keys
_FLEET_JOB_KEYS = {
    "name", "status", "events_committed", "windows", "frontier_ns", "wall_s",
}

# The closed set of dotted-key namespaces a metrics document may carry
# (docs/observability.md).  This is the single source of truth three
# consumers share: `validate_metrics_doc(strict_namespaces=True)` /
# `tools/validate_metrics.py --strict-namespaces` reject documents with
# keys outside it, and the shadowlint STL008 rule
# (shadow_tpu/analysis/rules.py) rejects the *emitting line* at lint
# time — so a new namespace lands here, with a schema-version bump and a
# docs row, before any code can emit it.
KNOWN_METRIC_NAMESPACES = frozenset({
    "engine",      # engine Counters struct (core/state.py)
    "obs",         # device counter block (obs/counters.py)
    "net",         # device network planes: net.nic/router/tcp.*
    "vtime",       # virtual-time roughness gauges
    "wall",        # driver wall-time histograms
    "round",       # per-dispatch-round throughput series
    "spill",       # spill-tier counters
    "gear",        # gearbox telemetry (schema v2)
    "faults",      # fault-tolerance plane (schema v3)
    "fleet",       # scenario-fleet scheduler plane (schema v4)
    "audit",       # determinism-audit plane (schema v5)
    "resilience",  # backend supervision (schema v6)
    "serve",       # sim-as-a-service daemon plane (schema v7)
    "pressure",    # resource-pressure degradation ladder (schema v8)
    "async",       # asynchronous conservative sync (schema v9)
    "balance",     # self-balancing fleet plane (schema v10)
    "mesh",        # multi-chip mesh execution plane (schema v11;
                   # elastic-resilience rows added in v12)
    "pipeline",    # pipelined CPU↔TPU handoff (schema v14)
    "hostplane",   # multi-worker host-plane drain (schema v15)
    "federation",  # federated serve plane / router (schema v16)
    "qdisc",       # per-interface scheduling plane (schema v17)
    "prof",        # profiling plane: histogram percentiles +
                   # critical-path posture (schema v18)
    "sim",         # build-level gauges (num_hosts, runahead)
})

# Histograms keep exact count/sum/min/max plus a bounded sample buffer for
# percentiles: past the cap, samples are kept with a deterministic stride
# (every k-th observation) — no RNG, reruns dump identical documents.
_SAMPLE_CAP = 4096


class Histogram:
    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._samples: list[float] = []
        self._stride = 1

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        if (self.count - 1) % self._stride == 0:
            self._samples.append(v)
            if len(self._samples) >= _SAMPLE_CAP:
                # decimate in place, double the stride
                self._samples = self._samples[::2]
                self._stride *= 2

    def summary(self) -> dict:
        if self.count == 0:
            return {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                    "mean": 0.0, "p50": 0.0, "p90": 0.0, "p99": 0.0}
        s = np.asarray(self._samples, dtype=np.float64)
        p50, p90, p99 = np.percentile(s, [50, 90, 99])
        return {
            "count": int(self.count),
            "sum": float(self.total),
            "min": float(self.min),
            "max": float(self.max),
            "mean": float(self.total / self.count),
            "p50": float(p50),
            "p90": float(p90),
            "p99": float(p99),
        }


class MetricsRegistry:
    def __init__(self):
        self.counters: dict[str, int] = {}
        self.gauges: dict[str, float | int] = {}
        self._hists: dict[str, Histogram] = {}
        # structured top-level sections (schema v4: "fleet"); absent from
        # the doc until set, so solo-run documents are unchanged
        self.sections: dict[str, dict] = {}

    def section_set(self, name: str, value: dict) -> None:
        self.sections[name] = dict(value)

    def counter_set(self, name: str, value: int) -> None:
        self.counters[name] = int(value)

    def counter_add(self, name: str, delta: int) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(delta)

    def gauge_set(self, name: str, value) -> None:
        self.gauges[name] = value

    def histogram(self, name: str) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = Histogram()
        return h

    def to_doc(self, meta: dict | None = None) -> dict:
        return {
            "kind": DOC_KIND,
            "schema_version": SCHEMA_VERSION,
            "created_unix": time.time(),
            "meta": dict(meta or {}),
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "histograms": {
                k: h.summary() for k, h in sorted(self._hists.items())
            },
            **{k: dict(v) for k, v in sorted(self.sections.items())},
        }

    def dump(self, path: str, meta: dict | None = None) -> dict:
        doc = self.to_doc(meta)
        dump_json_atomic(path, doc)
        return doc


def dump_json_atomic(path: str, doc: dict, indent: int | None = 1) -> None:
    """tmp + fsync + rename, the checkpoint plane's torn-write discipline
    (core/checkpoint.py): a poller or perf_compare reading
    `path` concurrently sees either the previous complete document or
    this one — never a truncated JSON."""
    import os

    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=indent)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


_HIST_KEYS = {"count", "sum", "min", "max", "mean", "p50", "p90", "p99"}


def validate_metrics_doc(doc: dict, strict_namespaces: bool = False) -> None:
    """Raise ValueError unless `doc` conforms to the documented schema
    (docs/observability.md). The tier-1 smoke test runs this on the
    --metrics-out output of the flagship tiny config.

    With `strict_namespaces`, every dotted counter/gauge/histogram key
    must additionally live in KNOWN_METRIC_NAMESPACES — the runtime twin
    of shadowlint's STL008 static check."""
    if not isinstance(doc, dict):
        raise ValueError("metrics doc must be a JSON object")
    if doc.get("kind") != DOC_KIND:
        raise ValueError(f"metrics doc kind {doc.get('kind')!r} != {DOC_KIND!r}")
    if doc.get("schema_version") != SCHEMA_VERSION:
        raise ValueError(
            f"metrics schema_version {doc.get('schema_version')!r} != "
            f"{SCHEMA_VERSION}"
        )
    for section in ("meta", "counters", "gauges", "histograms"):
        if not isinstance(doc.get(section), dict):
            raise ValueError(f"metrics doc section {section!r} missing or not an object")
    for k, v in doc["counters"].items():
        if not isinstance(v, int) or isinstance(v, bool):
            raise ValueError(f"counter {k!r} must be an integer, got {v!r}")
        if k.startswith("resilience.") and v < 0:
            # schema v6: backend-supervision counters are monotonic tallies
            raise ValueError(f"resilience counter {k!r} must be >= 0, got {v}")
        if k.startswith("serve.") and v < 0:
            # schema v7: daemon-plane counters are monotonic tallies too
            raise ValueError(f"serve counter {k!r} must be >= 0, got {v}")
        if k.startswith("pressure.") and v < 0:
            # schema v8: degradation-ladder counters are monotonic tallies
            raise ValueError(
                f"pressure counter {k!r} must be >= 0, got {v}"
            )
        if k.startswith("async.") and v < 0:
            # schema v9: async-sync counters are monotonic tallies
            raise ValueError(
                f"async counter {k!r} must be >= 0, got {v}"
            )
        if k.startswith("balance.") and v < 0:
            # schema v10: self-balancing counters are monotonic tallies
            raise ValueError(
                f"balance counter {k!r} must be >= 0, got {v}"
            )
        if k.startswith("mesh.") and v < 0:
            # schema v11: multi-chip counters are monotonic tallies
            raise ValueError(
                f"mesh counter {k!r} must be >= 0, got {v}"
            )
        if k.startswith("pipeline.") and v < 0:
            # schema v14: pipelined-handoff counters are monotonic tallies
            raise ValueError(
                f"pipeline counter {k!r} must be >= 0, got {v}"
            )
        if k.startswith("hostplane.") and v < 0:
            # schema v15: host-plane drain counters are monotonic tallies
            raise ValueError(
                f"hostplane counter {k!r} must be >= 0, got {v}"
            )
        if k.startswith("federation.") and v < 0:
            # schema v16: federated-serve counters are monotonic tallies
            raise ValueError(
                f"federation counter {k!r} must be >= 0, got {v}"
            )
        if k.startswith("qdisc.") and v < 0:
            # schema v17: qdisc counters are monotonic tallies
            raise ValueError(
                f"qdisc counter {k!r} must be >= 0, got {v}"
            )
        if k.startswith("prof.") and v < 0:
            # schema v18: profiling-plane counters are monotonic tallies
            raise ValueError(
                f"prof counter {k!r} must be >= 0, got {v}"
            )
    for k, v in doc["gauges"].items():
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            raise ValueError(f"gauge {k!r} must be a number, got {v!r}")
    for k, h in doc["histograms"].items():
        if not isinstance(h, dict) or not _HIST_KEYS <= set(h):
            raise ValueError(
                f"histogram {k!r} must carry keys {sorted(_HIST_KEYS)}"
            )
    if strict_namespaces:
        for section in ("counters", "gauges", "histograms"):
            for k in doc[section]:
                ns = k.split(".", 1)[0]
                if "." in k and ns not in KNOWN_METRIC_NAMESPACES:
                    raise ValueError(
                        f"{section} key {k!r}: namespace {ns!r} is not in "
                        f"KNOWN_METRIC_NAMESPACES (obs/metrics.py)"
                    )
    fleet = doc.get("fleet")
    if fleet is not None:
        # schema v4: fleet runs attach per-job rows (docs/observability.md)
        if not isinstance(fleet, dict) or not isinstance(
            fleet.get("jobs"), list
        ):
            raise ValueError(
                "fleet section must be an object with a jobs list"
            )
        for i, row in enumerate(fleet["jobs"]):
            if not isinstance(row, dict) or not _FLEET_JOB_KEYS <= set(row):
                raise ValueError(
                    f"fleet.jobs[{i}] must carry keys "
                    f"{sorted(_FLEET_JOB_KEYS)}"
                )
            audit = row.get("audit")
            if audit:
                # schema v5: a job's determinism-audit sub-object must at
                # least carry its integer digest chain (obs/audit.py)
                if not isinstance(audit, dict) or not isinstance(
                    audit.get("chain"), int
                ) or isinstance(audit.get("chain"), bool):
                    raise ValueError(
                        f"fleet.jobs[{i}].audit must carry an integer "
                        f"`chain` (the job's digest-chain value)"
                    )


def _sub_counter(reg: MetricsRegistry, sub, prefix: str, fields) -> None:
    for f in fields:
        v = getattr(sub, f, None)
        if v is not None:
            reg.counter_set(f"{prefix}.{f}", int(np.sum(np.asarray(v))))


def snapshot_device(sim, reg: MetricsRegistry) -> None:
    """Read every device-resident counter plane at a handoff boundary:
    engine Counters, the obs block, and the net-plane subs. One pass, no
    mid-window syncs — callers invoke this only between dispatches or at
    the end of a run."""
    import jax

    for k, v in sim.counters().items():
        reg.counter_set(f"engine.{k}", v)
    snap = obs_counters.snapshot(sim.state)
    if snap:
        for k, v in snap["win"].items():
            reg.counter_set(f"obs.{k}", v)
        he = snap["host_events"]
        reg.counter_set("obs.host_events_total", int(he.sum()))
        reg.gauge_set("obs.host_events_min", int(he.min()))
        reg.gauge_set("obs.host_events_max", int(he.max()))
        reg.gauge_set("obs.host_events_mean", float(he.mean()))
        for k, v in obs_counters.vtime_stats(snap["host_last_t"]).items():
            reg.gauge_set(f"vtime.{k}", v)
        if "host_digest" in snap:
            # determinism audit (schema v5): the combined digest chain +
            # block version; per-handoff records ride --digest-out
            from shadow_tpu.obs import audit as audit_mod

            reg.gauge_set(
                "audit.chain", audit_mod.combine(snap["host_digest"])
            )
            reg.gauge_set("audit.block_version", int(snap["block_version"]))
    trail = getattr(sim, "audit", None)
    if trail is not None:
        reg.counter_set("audit.records", len(trail.records))
    spool = getattr(sim, "flight_spool", None)
    if spool is not None:
        for k, v in spool.stats().items():
            reg.counter_set(f"audit.flight_{k}", int(v))
    subs = sim.state.subs
    nic = subs.get("nic")
    if nic is not None:
        nic = jax.device_get(nic)
        _sub_counter(reg, nic, "net.nic",
                     ("tx_packets", "tx_bytes", "rx_packets", "rx_bytes",
                      "sendq_dropped"))
    router = subs.get("router")
    if router is not None:
        _sub_counter(reg, jax.device_get(router), "net.router",
                     ("codel_dropped", "deep_high_water"))
    tcp = subs.get("tcp")
    if tcp is not None:
        _sub_counter(reg, jax.device_get(tcp), "net.tcp",
                     ("retransmits", "timeouts", "rtx_fast", "rtx_sack",
                      "rtx_walk", "drop_no_socket", "drop_ooo",
                      "accept_overflow"))
    reg.gauge_set("sim.num_hosts", int(sim.num_hosts))
    reg.gauge_set("sim.runahead_ns", int(sim.runahead))
    for k, v in sim.spill_stats().items():
        reg.counter_set(f"spill.{k}", int(v))
    gear_stats = getattr(sim, "gear_stats", None)
    if gear_stats is not None:
        g = gear_stats()
        reg.gauge_set("gear.level", int(g["gear_level"]))
        reg.gauge_set("gear.tiers", int(g["gear_tiers"]))
        reg.gauge_set("gear.capacity", int(g["gear_capacity"]))
        reg.counter_set("gear.shifts", int(g["gear_shifts"]))
        for lvl, n in g["gear_dispatches"].items():
            reg.counter_set(f"gear.dispatches.level{lvl}", int(n))
    # fault-tolerance plane (schema v3): injections fired, quarantines,
    # drained events, auto-checkpoint ring activity (shadow_tpu/faults)
    fault_stats = getattr(sim, "fault_stats", None)
    if fault_stats is not None:
        for k, v in fault_stats().items():
            reg.counter_set(f"faults.{k}", int(v))
    # backend supervision (schema v6): retries/backoffs/stalls/drains/
    # failovers/downtime from the attached supervisor (core/supervisor.py)
    res_stats = getattr(sim, "resilience_stats", None)
    if res_stats is not None:
        for k, v in res_stats().items():
            reg.counter_set(f"resilience.{k}", int(v))
    _snapshot_pressure(sim, reg)
    _snapshot_async(sim, reg)
    _snapshot_balance(sim, reg)
    _snapshot_mesh(sim, reg)
    _snapshot_pipeline(sim, reg)
    _snapshot_hostplane(sim, reg)
    _snapshot_qdisc(sim, reg)


def _snapshot_qdisc(sim, reg: MetricsRegistry) -> None:
    """Per-interface scheduling plane (schema v17): admission/service/
    drop tallies plus queue-occupancy gauges from the device queue
    discipline's [H]-leading counter plane (net/qdisc/). FIFO/round-robin
    runs carry no `qdisc` sub and emit no qdisc.* keys — pre-v17 docs
    stay valid."""
    import jax

    state = getattr(sim, "state", None)
    qd = state.subs.get("qdisc") if state is not None else None
    if qd is None:
        return
    qd = jax.device_get(qd)
    for f in ("enqueues", "dequeues", "drops_overflow", "drops_red",
              "drops_codel"):
        reg.counter_set(f"qdisc.{f}", int(np.sum(np.asarray(qd[f]))))
    depth = (
        np.asarray(qd["q_len"], np.int64)
        if "q_len" in qd
        else np.sum(np.asarray(qd["q_valid"], np.int64), axis=-1)
    )
    reg.gauge_set("qdisc.depth_max", int(depth.max()))
    reg.gauge_set("qdisc.depth_mean", float(depth.mean()))
    deq = int(np.sum(np.asarray(qd["dequeues"])))
    reg.gauge_set(
        "qdisc.sojourn_mean_ns",
        float(np.sum(np.asarray(qd["sojourn_sum"])) / deq) if deq else 0.0,
    )


def _snapshot_hostplane(sim, reg: MetricsRegistry) -> None:
    """Multi-worker host-plane plane (schema v15): pool width, sharded
    drains, canonical-merge wall, per-worker drain wall, serial
    fallbacks and re-pins from the drain pool (core/hostplane.py).
    Serial runs (experimental.host_workers: 1) report {} and emit no
    hostplane keys."""
    hs = getattr(sim, "hostplane_stats", None)
    if hs is None:
        return
    for k, v in hs().items():
        reg.counter_set(f"hostplane.{k}", int(v))


def _snapshot_pipeline(sim, reg: MetricsRegistry) -> None:
    """Pipelined-handoff plane (schema v14): issued-ahead / overlap /
    forced-drain / recompute-discard tallies from the two-slot dispatch
    pipeline (core/pipeline.py). Serial runs (experimental.
    pipelined_dispatch: false) report {} and emit no pipeline keys."""
    ps = getattr(sim, "pipeline_stats", None)
    if ps is None:
        return
    for k, v in ps().items():
        reg.counter_set(f"pipeline.{k}", int(v))


def _snapshot_mesh(sim, reg: MetricsRegistry) -> None:
    """Multi-chip mesh plane (schema v11): per-chip committed-event
    balance, neighbor-only frontier-exchange volume/partners, placement
    cut cost, and exchange-schedule rebuilds, from the islands runner
    (parallel/islands.py mesh_stats/mesh_gauges; None = single shard).
    Schema v12 adds the elastic-resilience posture from the attached
    ElasticMeshRunner (parallel/elastic.py): chips up/total gauges and
    the chip-loss / relayout / re-expansion / downtime counters — these
    also ride the sim the S→1 endpoint fell back to (the global engine
    has no mesh_stats, but its elastic hook still reports)."""
    ms = getattr(sim, "mesh_stats", None)
    if ms is not None:
        stats = ms()
        if stats:
            for k, v in stats.items():
                reg.counter_set(f"mesh.{k}", int(v))
    mg = getattr(sim, "mesh_gauges", None)
    if mg is not None:
        gauges = mg()
        if gauges:
            for k, v in gauges.items():
                reg.gauge_set(f"mesh.{k}", v)
    el = getattr(sim, "elastic", None)
    if el is not None:
        for k, v in el.stats().items():
            reg.counter_set(f"mesh.{k}", int(v))
        for k, v in el.gauges().items():
            reg.gauge_set(f"mesh.{k}", v)


def _snapshot_balance(sim, reg: MetricsRegistry) -> None:
    """Self-balancing plane (schema v10): migration / rollback / hold
    counters plus controller posture gauges, from the islands balancer
    (parallel/balancer.py) or the fleet scheduler's packing + stealing
    tallies (fleet/scheduler.py; None/absent = no balance plane)."""
    bs = getattr(sim, "balance_stats", None)
    if bs is not None:
        stats = bs()
        if stats:
            for k, v in stats.items():
                reg.counter_set(f"balance.{k}", int(v))
    bg = getattr(sim, "balance_gauges", None)
    if bg is not None:
        gauges = bg()
        if gauges:
            for k, v in gauges.items():
                reg.gauge_set(f"balance.{k}", v)


def _snapshot_async(sim, reg: MetricsRegistry) -> None:
    """Asynchronous-conservative-sync plane (schema v9): superstep /
    shard-window / yield / blocked-on-neighbor counters plus frontier
    spread and lookahead gauges, from the islands driver or the fleet
    (parallel/islands.py async_stats/async_gauges; None = barrier)."""
    ast = getattr(sim, "async_stats", None)
    if ast is not None:
        stats = ast()
        if stats:
            for k, v in stats.items():
                reg.counter_set(f"async.{k}", int(v))
    ag = getattr(sim, "async_gauges", None)
    if ag is not None:
        gauges = ag()
        if gauges:
            for k, v in gauges.items():
                reg.gauge_set(f"async.{k}", v)


def _snapshot_pressure(sim, reg: MetricsRegistry) -> None:
    """Resource-pressure plane (schema v8): ladder counters from the
    attached controller plus the HBM estimate/headroom gauges
    (core/pressure.py) — the preflight budget the serve daemon's
    admission compares against."""
    from shadow_tpu.core import pressure as pressure_mod

    ps = getattr(sim, "pressure_stats", None)
    if ps is not None:
        for k, v in ps().items():
            reg.counter_set(f"pressure.{k}", int(v))
    pc = getattr(sim, "pressure", None)
    if pc is not None:
        for k, v in pc.gauges().items():
            reg.gauge_set(f"pressure.{k}", v)
    try:
        est = pressure_mod.estimate_hbm_bytes(sim)
    except Exception:
        return  # estimator is best-effort telemetry, never a run failure
    reg.gauge_set("pressure.estimated_bytes", int(est["total_bytes"]))
    hb = pressure_mod.headroom_bytes(est["total_bytes"])
    if hb is not None:
        reg.gauge_set("pressure.headroom_bytes", int(hb))


def snapshot_fleet(fleet, reg: MetricsRegistry) -> None:
    """Read a FleetSimulation's scheduler-plane results into the registry
    (schema v4): fleet.* counters plus the top-level `fleet` section with
    one `jobs[*]` row per experiment (per-job events / windows / virtual-
    time frontier). Per-job device counters ride inside each row — the
    fleet harvested them at the job's own handoff boundary, so this call
    never forces a sync."""
    stats = fleet.fleet_stats()
    for k in ("jobs_total", "jobs_done", "jobs_failed", "jobs_timeout",
              "lane_swaps", "admission_upshifts", "kernel_traces",
              "gear_shifts"):
        reg.counter_set(f"fleet.{k}", int(stats.get(k, 0)))
    reg.gauge_set("fleet.lanes", int(stats.get("lanes", 0)))
    reg.gauge_set("fleet.gear_level", int(stats.get("gear_level", 0)))
    # backend supervision (schema v6): supervisor counters + the
    # scheduler's deadline lane reclaims / drain requeues
    res_stats = getattr(fleet, "resilience_stats", None)
    if res_stats is not None:
        for k, v in res_stats().items():
            reg.counter_set(f"resilience.{k}", int(v))
    _snapshot_pressure(fleet, reg)
    _snapshot_async(fleet, reg)
    _snapshot_balance(fleet, reg)
    _snapshot_mesh(fleet, reg)
    _snapshot_pipeline(fleet, reg)
    _snapshot_hostplane(fleet, reg)
    _snapshot_qdisc(fleet, reg)
    reg.section_set("fleet", {
        "lanes": int(stats.get("lanes", 0)),
        "lane_swaps": int(stats.get("lane_swaps", 0)),
        "kernel_traces": int(stats.get("kernel_traces", 0)),
        "jobs": fleet.results(),
    })


class ObsSession:
    """The driver-facing handle: one per run, attached as
    `sim.obs_session`. Bundles the metrics registry with an optional
    Chrome tracer; the engine drivers call `span()` around each phase and
    `round_done()` after each dispatch round's handoff sync."""

    def __init__(self, metrics: MetricsRegistry | None = None, tracer=None,
                 prof=None):
        self.metrics = metrics or MetricsRegistry()
        self.tracer = tracer
        self.prof = prof  # optional obs/prof.ProfRecorder
        self._last_events = 0
        self._last_wall = time.perf_counter()
        self._dispatches = 0

    @contextmanager
    def span(self, name: str, **args):
        """Wall-time span: observed into `wall.{name}_s` and (when tracing)
        emitted as a Chrome complete event. The FIRST dispatch span also
        lands in `wall.first_dispatch_s` — it includes XLA compilation.
        It is also a `jax.profiler.TraceAnnotation`, so a profiler trace
        shows the span on the host plane, on the device ops' clock."""
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        tr = self.tracer.span(name, **args) if self.tracer else nullcontext()
        with tr, TraceAnnotation(name):
            yield
        dt = time.perf_counter() - t0
        self.metrics.histogram(f"wall.{name}_s").observe(dt)
        if self.prof is not None:
            # mergeable int64 twins (obs/hist.py): `dispatch` and the
            # pipelined driver's `await` are both device-wait wall
            if name in ("dispatch", "await"):
                self.prof.observe_wall("dispatch_wall_ns", dt)
            elif name == "host_drain":
                self.prof.observe_wall("host_drain_wall_ns", dt)
        if name == "dispatch":
            self._dispatches += 1
            if self._dispatches == 1:
                self.metrics.gauge_set("wall.first_dispatch_s", dt)

    def round_done(self, sim, frontier_ns: int | None = None) -> None:
        """Per-round throughput sample, taken at the handoff boundary the
        driver already synced at (the scalar frontier fetch). Drivers
        pass the committed frontier they fetched anyway; the profiling
        recorder stamps its interval ring with it."""
        now = time.perf_counter()
        ev = obs_counters.committed_counters(sim)["events_committed"]
        dt = now - self._last_wall
        if dt > 0 and ev > self._last_events:
            self.metrics.histogram("round.events_per_sec").observe(
                (ev - self._last_events) / dt
            )
        if self.tracer:
            self.tracer.counter(
                "progress", {"events_committed": int(ev)}
            )
        self._last_events, self._last_wall = ev, now
        if self.prof is not None:
            self.prof.tick_from(sim, frontier_ns=frontier_ns)

    def finalize(self, sim) -> None:
        snapshot_device(sim, self.metrics)
        if self.prof is not None:
            snapshot_prof(self.prof, self.metrics)


def snapshot_prof(prof, reg: MetricsRegistry) -> None:
    """Profiling plane (schema v18): fold the recorder's mergeable
    histograms into prof.* percentile gauges, the interval-ring posture
    counters, and — when the run carried per-shard async data — the
    critical-path attribution posture (obs/prof.critical_path)."""
    from shadow_tpu.obs import prof as prof_mod

    for name, h in sorted(prof._hists.items()):
        if not h.count:
            continue
        s = h.summary()
        reg.counter_set(f"prof.{name}_count", s["count"])
        for q in ("p50", "p90", "p99", "max"):
            reg.gauge_set(f"prof.{name}_{q}", int(s[q]))
    reg.counter_set("prof.intervals", int(prof.recorded))
    reg.counter_set("prof.dropped", int(prof.dropped))
    cp = prof_mod.critical_path(prof.to_doc())
    if cp is not None:
        reg.gauge_set("prof.critical_shard", int(cp["critical_shard"]))
        reg.gauge_set("prof.blocked_frac", float(cp["blocked_frac"]))
        reg.gauge_set("prof.wall_frac", float(cp["wall_frac"]))


def span(session: ObsSession | None, name: str, **args):
    """Null-safe span: drivers call this unconditionally; with no session
    attached it is a nullcontext — zero overhead on the default path."""
    return session.span(name, **args) if session is not None else nullcontext()
