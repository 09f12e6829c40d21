"""The sim-as-a-service daemon: a crash-safe, multi-tenant fleet host.

``python -m shadow_tpu serve --state-dir DIR`` starts a resident process
that accepts sweep jobs over a local HTTP-over-unix-socket API
(tools/shadowctl.py is the operator client) and owns a fleet scheduler
across restarts. Three mechanisms make its own death a non-event:

1. **Write-ahead journal** (serve/journal.py): every scheduler
   transition — submit, admit, drain, requeue, complete — is fsync'd to
   an append-only CRC-framed log before it takes effect. `kill -9` the
   daemon, restart it, and replay re-queues unfinished sweeps and
   re-attaches in-flight fleets via their checkpoint directories; the
   finished sweep's per-job audit digest chains are bit-identical to an
   uninterrupted run (tests/test_serve.py, bench.py --serve-smoke).

2. **AOT kernel cache** (serve/kcache.py): fleet window kernels bind
   from serialized exports keyed by (config digest, gear, avals, jaxlib
   version). A warm restart re-binds every known fleet shape with ZERO
   Python traces — `kernel_traces` stays 0 — and a corrupt or
   version-skewed entry is evicted and recompiled, never trusted.

3. **Graceful degradation**: SIGTERM drains the running fleet to its
   checkpoint (one dispatch of latency, then a clean exit whose journal
   DRAIN record lets the next boot resume); admission applies per-tenant
   quotas, queue-depth backpressure AND memory-aware preflight (a sweep
   whose estimated HBM footprint exceeds the live headroom —
   core/pressure.estimate_config_bytes vs device_memory_budget — sheds
   HTTP 429 `memory_pressure` instead of OOMing mid-run); `/healthz`
   reports backend liveness (the supervisor probe of core/supervisor.py
   — the cs/0409032 bounded-lag signal), queue depth, journal lag, the
   memory-headroom gauges, and the running fleet's pressure-ladder
   posture (journaled as PRESSURE records as rungs fire). Backend loss mid-sweep rides the PR-6 supervision
   plane: the fleet drains, the sweep is journaled REQUEUE, and the
   worker retries it — `kill_backend` fault plans submitted with a sweep
   drive this end to end in chaos tests.

Metrics ride the schema-v8 `serve.*` + `pressure.*` namespaces
(obs/metrics.py), dumped
to `<state-dir>/serve.metrics.json` at every sweep settlement.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import socketserver
import threading
import time
from http.server import BaseHTTPRequestHandler

from shadow_tpu.serve import journal as journal_mod
from shadow_tpu.serve.kcache import KernelCache, cache_root

JOURNAL_NAME = "journal.wal"
METRICS_NAME = "serve.metrics.json"

# EWMA seed for the Retry-After estimate before any sweep has finished
_DEFAULT_SWEEP_WALL_S = 30.0
_EWMA_ALPHA = 0.3


class ServeError(ValueError):
    pass


class ServeOptions:
    """Daemon configuration (CLI flags / ServeOptions kwargs)."""

    def __init__(
        self,
        state_dir: str,
        socket_path: str | None = None,
        lanes: int | None = None,
        max_queue_depth: int = 16,
        default_quota: int = 8,
        tenant_quotas: dict[str, int] | None = None,
        checkpoint_every_dispatches: int = 4,
        cache_dir: str | None = None,
    ):
        self.state_dir = os.path.abspath(state_dir)
        self.socket_path = socket_path or os.path.join(
            self.state_dir, "serve.sock"
        )
        self.lanes = lanes
        self.max_queue_depth = int(max_queue_depth)
        self.default_quota = int(default_quota)
        self.tenant_quotas = dict(tenant_quotas or {})
        self.checkpoint_every_dispatches = max(
            1, int(checkpoint_every_dispatches)
        )
        self.cache_dir = cache_dir or cache_root()


class ShadowDaemon:
    """One resident daemon: journal + queue + worker + API server."""

    def __init__(self, opts: ServeOptions):
        self.opts = opts
        os.makedirs(opts.state_dir, exist_ok=True)
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._draining = threading.Event()
        self.journal = journal_mod.Journal(
            os.path.join(opts.state_dir, JOURNAL_NAME)
        )
        self.kcache = KernelCache(opts.cache_dir)
        self.counters = {
            "sweeps_submitted": 0,
            "sweeps_completed": 0,
            "sweeps_failed": 0,
            "sweeps_requeued": 0,
            "sweeps_drained": 0,
            "jobs_completed": 0,
            "sheds": 0,
            "memory_sheds": 0,
            "sweeps_handed_off": 0,
            "pressure_records": 0,
            "balance_records": 0,
            "journal_replays": 0,
            "kernel_traces": 0,
        }
        # memory-aware admission (core/pressure.py, docs/serving.md): the
        # running sweep's preflight HBM estimate, compared against the
        # device budget when one is known; updated on admit/settle. The
        # last published pressure-ladder posture rides /healthz.
        self._running_est_bytes = 0
        self._last_pressure: dict = {}
        self._journaled_pressure: dict[str, int] = {}
        # self-balancing plane (ISSUE 11): the running fleet's balance
        # posture (lane steals, packing decisions) + async posture
        # (frontier spread, laggard shard) for /healthz and shadowctl
        # status; BALANCE journal records mirror the PRESSURE pattern
        self._last_balance: dict = {}
        # elastic mesh posture (schema v12): chips up/total + last
        # relayout from the running fleet, for /healthz + /metricz and
        # the surviving-chip admission budget scale
        self._last_mesh: dict = {}
        self._last_async: dict = {}
        # the running fleet's lane-steal posture lifted for the router
        # (fleet/scheduler.steal_export): queued predicted load is the
        # cross-daemon steal ordering signal (serve/federation.py)
        self._last_steal: dict = {}
        self._journaled_balance: dict[str, int] = {}
        # replay: fold the journal into scheduler-plane truth
        st = self.journal.state()
        self.sweeps: dict[str, dict] = {
            sid: dict(st.sweeps[sid]) for sid in st.order
        }
        self._order: list[str] = list(st.order)
        self._queue: list[str] = [s["id"] for s in st.unfinished()]
        if self._queue or self.journal.torn_tail_dropped:
            self.counters["journal_replays"] = 1
        self._seq = len(self._order)
        self._running: str | None = None
        self._avg_sweep_wall_s = _DEFAULT_SWEEP_WALL_S
        self._server: socketserver.ThreadingMixIn | None = None
        self._started = threading.Event()
        # shadowscope profiling plane (obs/prof.py): request-latency
        # histograms + a per-dispatch-slice interval ring ticked from
        # the running fleet, served live at GET /timez and rolled up
        # across peers by the federation router. Guarded by self._lock
        # (the recorder itself is not thread-safe).
        from shadow_tpu.obs import prof as prof_mod

        self.prof = prof_mod.ProfRecorder()

    # ------------------------------------------------------------------
    # admission (HTTP thread)
    # ------------------------------------------------------------------

    def _tenant_load(self, tenant: str) -> int:
        return sum(
            1 for s in self.sweeps.values()
            if s["tenant"] == tenant
            and s["status"] in ("queued", "running", "drained")
        )

    def retry_after_s(self) -> int:
        """Backpressure hint: how long until a queue slot likely frees —
        queue depth (sweeps ahead) x the EWMA completed-sweep wall.
        Zero when the daemon is idle: an empty queue has no wait, and
        the federation router's placement score must see an idle peer
        as immediately available, not penalized by its sweep-wall EWMA."""
        depth = len(self._queue) + (1 if self._running else 0)
        if depth == 0:
            return 0
        return max(1, int(round(depth * self._avg_sweep_wall_s)))

    def _shed_retry_after_s(self) -> int:
        """Retry hint for SHED responses: never 0 — a rejected client
        told to retry in 0 s would hot-spin against the same refusal.
        Only /healthz reports the raw 0-at-idle value, for placement."""
        return max(1, self.retry_after_s())

    def _effective_budget(self):
        """The admission memory budget, scaled to the SURVIVING mesh
        (schema v12): a fleet degraded to 7 of 8 chips holds 7 chips'
        HBM, so admission must not fill the dead chip's share — budget ×
        chips_up / chips_total whenever the mesh posture reports a
        loss. None when the backend reports no limit."""
        from shadow_tpu.core import pressure as pressure_mod

        budget = pressure_mod.device_memory_budget()
        m = self._last_mesh
        if (budget is not None and m
                and int(m.get("chips_total", 0)) > 0):
            budget = (budget * int(m.get("chips_up", 0))
                      ) // int(m["chips_total"])
        return budget

    def _memory_view(self) -> dict:
        """The /healthz memory-headroom gauges (docs/serving.md): device
        budget (scaled to the surviving mesh), the running sweep's
        preflight estimate, and live headroom (nulls when the backend
        reports no limit)."""
        budget = self._effective_budget()
        return {
            "budget_bytes": budget,
            "estimated_running_bytes": int(self._running_est_bytes),
            "headroom_bytes": (
                budget - int(self._running_est_bytes)
                if budget is not None else None
            ),
        }

    @staticmethod
    def _estimate_sweep_bytes(jobs, lanes) -> int:
        """Preflight footprint of a sweep: per-job config estimate x the
        lane count it will occupy (core/pressure.estimate_config_bytes)."""
        from shadow_tpu.core.config import load_config
        from shadow_tpu.core import pressure as pressure_mod

        cfg = load_config(jobs[0].config)
        L = min(len(jobs), lanes) if lanes else len(jobs)
        return pressure_mod.estimate_config_bytes(cfg, lanes=L)

    def submit(self, doc: dict, tenant: str = "default",
               backend_faults: list | None = None,
               origin: str | None = None) -> dict:
        """Validate + journal + enqueue one sweep. Raises ServeError
        (HTTP 400) on a bad document; returns {"shed": ...} (HTTP 429)
        when admission refuses it.

        `origin` is the federation handoff marker (serve/federation.py):
        a sweep re-placed here after a steal or peer-loss failover
        carries its origin handle, journaled with the SUBMIT record, so
        the router's crash recovery can prove the handoff landed instead
        of re-submitting it (the no-duplicate half of the steal
        contract). A sweep with an origin already present in this
        journal is refused as a duplicate."""
        from shadow_tpu.fleet import SweepError, load_sweep

        with self._lock:
            if self._draining.is_set():
                self.counters["sheds"] += 1
                return {"shed": "draining", "retry_after_s": 30}
            if origin is not None:
                for s in self.sweeps.values():
                    if s.get("origin") == origin:
                        # handoff replayed by the router's crash
                        # recovery: the first landing is the claim
                        return {"id": s["id"], "duplicate": True}
            depth = len(self._queue) + (1 if self._running else 0)
            if depth >= self.opts.max_queue_depth:
                self.counters["sheds"] += 1
                return {
                    "shed": "queue_full",
                    "queue_depth": depth,
                    "retry_after_s": self._shed_retry_after_s(),
                }
            quota = self.opts.tenant_quotas.get(
                tenant, self.opts.default_quota
            )
            if self._tenant_load(tenant) >= quota:
                self.counters["sheds"] += 1
                return {
                    "shed": "tenant_quota",
                    "quota": quota,
                    "retry_after_s": self._shed_retry_after_s(),
                }
        # expansion/validation is pure host work: do it OUTSIDE the lock
        # (a slow config build must not block /healthz), and fail the
        # submission here with the offending job named — never mid-fleet
        try:
            jobs, sweep_opts = load_sweep(doc)
        except (SweepError, ValueError) as e:
            raise ServeError(str(e)) from e
        if backend_faults:
            from shadow_tpu.faults import plan as plan_mod

            # kill_chip targets bounds-check against the sweep's own
            # mesh size (experimental.num_shards; None = no mesh, any
            # kill_chip is then refused by the range check at size 0).
            # A bad plan is a CLIENT error: fold it into ServeError so
            # the HTTP layer answers 400 instead of the handler thread
            # dying connection-open (pre-elastic the same escape killed
            # the thread on any malformed backend_faults list).
            exp = (jobs[0].config.get("experimental") or {})
            mesh_size = int(exp.get("num_shards", 1) or 1)
            try:
                plan_mod.check_backend_ops(
                    plan_mod.parse_fault_plan(backend_faults),
                    mesh_size=mesh_size if mesh_size > 1 else None,
                )
            except plan_mod.FaultPlanError as e:
                raise ServeError(f"backend_faults: {e}") from e
        # memory-aware admission (docs/serving.md): preflight the sweep's
        # HBM footprint against the live headroom — a sweep the device
        # cannot place sheds NOW with a 429, instead of OOMing mid-run
        lanes = self.opts.lanes or (
            int(sweep_opts["lanes"]) if sweep_opts.get("lanes") else None
        )
        try:
            est_bytes = self._estimate_sweep_bytes(jobs, lanes)
        except (ValueError, OSError):
            est_bytes = 0  # advisory: a truly bad config failed above
        budget = self._effective_budget()
        with self._lock:
            if budget is not None \
                    and est_bytes > budget - self._running_est_bytes:
                self.counters["sheds"] += 1
                self.counters["memory_sheds"] += 1
                return {
                    "shed": "memory_pressure",
                    "estimated_bytes": int(est_bytes),
                    "headroom_bytes": int(
                        budget - self._running_est_bytes
                    ),
                    "retry_after_s": self._shed_retry_after_s(),
                }
            sid = f"s{self._seq:06d}"
            self._seq += 1
            extra = {"origin": origin} if origin is not None else {}
            self.journal.append(
                journal_mod.SUBMIT, id=sid, tenant=tenant, doc=doc,
                backend_faults=backend_faults or [], **extra,
            )
            self.sweeps[sid] = {
                "id": sid, "tenant": tenant, "doc": doc,
                "status": "queued", "ckpt_dir": None, "results": None,
                "admits": 0, "backend_faults": backend_faults or [],
                **extra,
            }
            self._order.append(sid)
            self._queue.append(sid)
            self.counters["sweeps_submitted"] += 1
            self._wake.notify_all()
            return {"id": sid, "jobs": len(jobs),
                    "queue_position": len(self._queue) - 1}

    def release_sweep(self, sid: str, to_peer: str) -> dict | None:
        """Hand a QUEUED sweep to another federation member (the router's
        work-steal / rebalance pull, serve/federation.py). The HANDOFF
        record is journaled BEFORE the sweep leaves the queue — the
        torn-tail discipline of PR 8 applied to stealing: a crash after
        this append can never run the sweep here again (replay folds
        `handed_off`, which `unfinished()` skips), and a crash BEFORE it
        leaves nothing for the receiver to duplicate. Returns the full
        journaled document (the receiver re-submits it under its own
        journal); None when the sweep is unknown, and a `busy` marker
        when it is not queued (running/settled sweeps are never stolen —
        their checkpoints live in THIS daemon's state-dir)."""
        with self._lock:
            s = self.sweeps.get(sid)
            if s is None:
                return None
            if s["status"] != "queued" or sid not in self._queue:
                return {"busy": s["status"]}
            self.journal.append(
                journal_mod.HANDOFF, id=sid, to_peer=str(to_peer),
            )
            self._queue.remove(sid)
            s["status"] = "handed_off"
            s["handoff_to"] = str(to_peer)
            self.counters["sweeps_handed_off"] += 1
            return {
                "id": sid, "tenant": s["tenant"], "doc": s["doc"],
                "backend_faults": s.get("backend_faults") or [],
            }

    # ------------------------------------------------------------------
    # introspection (HTTP thread)
    # ------------------------------------------------------------------

    def journal_doc(self) -> dict:
        """The journal as JSON (GET /v1/journal): the peer-to-peer
        journal copy the federation router mirrors on every probe, so a
        peer whose state-dir becomes unreadable with the box can still
        be replayed from the router's last mirror."""
        with self._lock:
            return {
                "records": self.journal.records,
                "torn_tail_dropped": self.journal.torn_tail_dropped,
            }

    def health(self) -> dict:
        from shadow_tpu.core.supervisor import probe_backend

        import jax

        probe_ok = probe_backend()
        with self._lock:
            by_status: dict[str, int] = {}
            for s in self.sweeps.values():
                by_status[s["status"]] = by_status.get(s["status"], 0) + 1
            return {
                "ok": probe_ok and not self._draining.is_set(),
                "draining": self._draining.is_set(),
                "backend": {
                    "platform": jax.default_backend(),
                    "probe_ok": probe_ok,
                },
                "queue": {
                    "depth": len(self._queue),
                    "running": self._running,
                    "sweeps": by_status,
                },
                "journal": {
                    "records": len(self.journal.records),
                    "lag": self.journal.lag(),
                    "torn_tail_dropped": self.journal.torn_tail_dropped,
                },
                "kcache": self.kcache.stats(),
                "memory": self._memory_view(),
                "pressure": dict(self._last_pressure),
                "balance": dict(self._last_balance),
                "async": dict(self._last_async),
                "mesh": dict(self._last_mesh),
                "steal": dict(self._last_steal),
                "prof": self._prof_posture(),
                "retry_after_s": self.retry_after_s(),
            }

    def _prof_posture(self) -> dict:
        """Critical-path posture for /healthz (caller holds the lock):
        which shard the running fleet's wall is attributable to and the
        blocked fraction of all shard-supersteps; -1/0.0 before any
        per-shard interval lands (barrier fleets, idle daemon)."""
        from shadow_tpu.obs import prof as prof_mod

        cp = prof_mod.critical_path(self.prof.to_doc())
        if cp is None:
            return {"critical_shard": -1, "blocked_frac": 0.0}
        return {
            "critical_shard": int(cp["critical_shard"]),
            "blocked_frac": round(float(cp["blocked_frac"]), 4),
            "wall_frac": round(float(cp["wall_frac"]), 4),
        }

    def timez_doc(self) -> dict:
        """The live profile document (GET /timez): the interval ring +
        histograms as a schema-versioned shadow_tpu.profile doc — the
        unit the federation router merges across peers."""
        with self._lock:
            return self.prof.to_doc(meta={"daemon": "shadow_tpu serve"})

    def _observe_request(self, dt_s: float) -> None:
        with self._lock:
            self.prof.observe_wall("serve_request_ns", dt_s)

    def sweep_info(self, sid: str) -> dict | None:
        with self._lock:
            s = self.sweeps.get(sid)
            return dict(s) if s is not None else None

    def sweep_list(self) -> list[dict]:
        with self._lock:
            return [
                {k: self.sweeps[sid][k]
                 for k in ("id", "tenant", "status")}
                | {"progress": self.sweeps[sid].get("progress")}
                for sid in self._order
            ]

    def metrics_doc(self) -> dict:
        from shadow_tpu.obs import metrics as obs_metrics

        reg = obs_metrics.MetricsRegistry()
        with self._lock:
            for k, v in self.counters.items():
                reg.counter_set(f"serve.{k}", int(v))
            for k, v in self.kcache.stats_counters.items():
                reg.counter_set(f"serve.kcache_{k}", int(v))
            reg.counter_set(
                "serve.journal_records", len(self.journal.records)
            )
            reg.gauge_set("serve.queue_depth", len(self._queue))
            reg.gauge_set("serve.journal_lag", self.journal.lag())
            reg.gauge_set(
                "serve.draining", int(self._draining.is_set())
            )
            reg.gauge_set("serve.kcache_entries", self.kcache.entries())
            # pressure plane (schema v8): the memory-headroom gauges the
            # memory-aware admission compares, + ladder posture counters
            mem = self._memory_view()
            reg.gauge_set(
                "pressure.estimated_bytes", mem["estimated_running_bytes"]
            )
            if mem["headroom_bytes"] is not None:
                reg.gauge_set(
                    "pressure.headroom_bytes", mem["headroom_bytes"]
                )
            for k, v in self._last_pressure.items():
                reg.counter_set(f"pressure.{k}", int(v))
            # mesh plane (schema v12): chips up/total + elastic
            # relayout posture of the running fleet
            for k, v in self._last_mesh.items():
                if k in ("chips_up", "chips_total", "shard_map"):
                    reg.gauge_set(f"mesh.{k}", int(v))
                elif k in ("exchange_rebuilds", "relayouts",
                           "re_expansions"):
                    reg.counter_set(f"mesh.{k}", int(v))
            # balance plane (schema v10): the running fleet's packing +
            # steal tallies ("packing" is a string — gauge-encoded)
            for k, v in self._last_balance.items():
                if k == "packing":
                    reg.gauge_set("balance.packing_load",
                                  int(v == "load"))
                else:
                    reg.counter_set(f"balance.{k}", int(v))
            # profiling plane (schema v18): latency percentiles +
            # critical-path posture folded from the live recorder
            obs_metrics.snapshot_prof(self.prof, reg)
        return reg.to_doc(meta={"daemon": "shadow_tpu serve"})

    def _dump_metrics(self) -> None:
        from shadow_tpu.obs.metrics import dump_json_atomic

        doc = self.metrics_doc()
        path = os.path.join(self.opts.state_dir, METRICS_NAME)
        dump_json_atomic(path, doc)

    # ------------------------------------------------------------------
    # the worker (main thread): one sweep at a time, drained on SIGTERM
    # ------------------------------------------------------------------

    def drain(self) -> None:
        """Initiate graceful shutdown: the worker flushes the running
        fleet to its checkpoint, journals DRAIN, and exits. Runs from
        signal handlers, which execute ON the worker thread — possibly
        while it holds the lock, so an unbounded blocking acquire could
        deadlock against ourselves and a non-blocking one silently skips
        the wake-up whenever an HTTP thread holds the lock (the race the
        STH004 lint flags). A bounded acquire gets both: mutual
        exclusion whenever the lock frees within the timeout, and a
        guaranteed return either way — the worker polls the event every
        0.25 s slice, so a skipped notify only delays, never loses, the
        drain."""
        self._draining.set()
        if self._lock.acquire(timeout=1.0):
            try:
                self._wake.notify_all()
            finally:
                self._lock.release()

    def _sweep_ckpt_dir(self, sid: str) -> str:
        return os.path.join(self.opts.state_dir, "sweeps", sid)

    def _build_or_resume(self, s: dict):
        """A FleetSimulation for the sweep: re-attached from its
        checkpoint directory when a previous incarnation left slices
        there, else built fresh from the journaled document."""
        from shadow_tpu.core.checkpoint import CheckpointError
        from shadow_tpu.core.config import load_config
        from shadow_tpu.fleet import build_fleet, load_sweep, resume_fleet
        from shadow_tpu.fleet import checkpoint as fleet_ckpt

        ckpt_dir = self._sweep_ckpt_dir(s["id"])
        jobs, sweep_opts = load_sweep(s["doc"])
        fopts = load_config(jobs[0].config).fleet
        lanes = self.opts.lanes or (
            int(sweep_opts["lanes"]) if sweep_opts.get("lanes")
            else (fopts.lanes or None)
        )
        if os.path.exists(os.path.join(ckpt_dir, fleet_ckpt.MANIFEST)):
            try:
                fleet = resume_fleet(
                    ckpt_dir, lanes=lanes,
                    windows_per_dispatch=fopts.windows_per_dispatch,
                )
            except CheckpointError as e:
                if "already terminal" in str(e):
                    # the crash landed between the final manifest write
                    # and the COMPLETE record: the results are all in the
                    # manifest — settle from it without re-running
                    doc = fleet_ckpt.load_manifest(ckpt_dir)
                    return None, doc, fopts
                raise
        else:
            fleet = build_fleet(jobs, lanes=lanes,
                                windows_per_dispatch=fopts.windows_per_dispatch,
                                checkpoint_dir=ckpt_dir)
        fleet.attach_kernel_cache(self.kcache)
        # the daemon is the loop's outer ring (parallel/balancer.py's
        # inner loop heals shards; this packs whole jobs): freed lanes
        # take the heaviest pending job by predicted load, and an early-
        # finishing lane steals ahead of FIFO order (fleet/scheduler.py)
        fleet.sched.packing = "load"
        if s.get("backend_faults"):
            from shadow_tpu.faults import plan as plan_mod

            fleet.attach_faults(
                plan_mod.parse_fault_plan(s["backend_faults"])
            )
        return fleet, None, fopts

    def _publish_progress(self, sid: str, fleet) -> None:
        st = fleet.sched.stats()
        pst = fleet.pressure_stats()
        bst = fleet.balance_stats() or {}
        with self._lock:
            self.sweeps[sid]["progress"] = {
                "jobs_done": st["jobs_done"],
                "jobs_running": st["jobs_running"],
                "jobs_queued": st["jobs_queued"],
                "kernel_traces": fleet.kernel_traces,
                "pressure_steps": int(pst.get("ladder_steps", 0)),
                "lane_steals": int(st.get("lane_steals", 0)),
            }
            self._last_pressure = pst
            self._last_balance = {
                "packing": fleet.sched.packing, **bst,
            }
            self._last_async = fleet.async_posture()
            self._last_mesh = fleet.mesh_posture()
            self._last_steal = fleet.sched.steal_export()
            # one profiling-plane interval per dispatch slice: deltas of
            # the fleet's committed events + async counters, with the
            # per-(shard) frontier surface when the fleet runs async
            self.prof.tick_from(fleet)
            # journal each new batch of ladder rungs: a post-mortem can
            # see WHEN the sweep started degrading even if we die next
            steps = int(pst.get("ladder_steps", 0))
            if steps > self._journaled_pressure.get(sid, 0):
                self._journaled_pressure[sid] = steps
                self.journal.append(
                    journal_mod.PRESSURE, id=sid, steps=steps, counters=pst
                )
                self.counters["pressure_records"] += 1
            # likewise each new balance action (migration, rollback or
            # lane steal): the journal shows WHEN healing started
            acts = sum(int(v) for v in bst.values())
            if acts > self._journaled_balance.get(sid, 0):
                self._journaled_balance[sid] = acts
                self.journal.append(
                    journal_mod.BALANCE, id=sid, actions=acts, counters=bst
                )
                self.counters["balance_records"] += 1

    def _run_sweep(self, sid: str) -> None:
        from shadow_tpu.core.checkpoint import CheckpointError
        from shadow_tpu.core.supervisor import BackendLost
        from shadow_tpu.fleet import FleetError, SweepError, save_fleet

        s = self.sweeps[sid]
        ckpt_dir = self._sweep_ckpt_dir(sid)
        t0 = time.monotonic()
        with self._lock:
            self._running = sid
            s["status"] = "running"
            s["ckpt_dir"] = ckpt_dir
            self.journal.append(
                journal_mod.ADMIT, id=sid, ckpt_dir=ckpt_dir
            )
        fleet = None
        try:
            fleet, settled_manifest, fopts = self._build_or_resume(s)
            if fleet is None:
                self._settle_from_manifest(sid, settled_manifest)
                return
            # the live footprint the admission check subtracts from the
            # device budget while this sweep runs (docs/serving.md)
            from shadow_tpu.core import pressure as pressure_mod

            try:
                est = pressure_mod.estimate_hbm_bytes(fleet)["total_bytes"]
            except Exception:
                est = 0
            with self._lock:
                self._running_est_bytes = est
            # first manifest BEFORE the first dispatch: a kill landing
            # anywhere after this point re-attaches instead of rebuilding
            save_fleet(fleet, ckpt_dir)
            optimistic = fopts.sync == "optimistic"
            slices = 0
            while not fleet.sched.all_terminal():
                if self._draining.is_set():
                    save_fleet(fleet, ckpt_dir)
                    with self._lock:
                        s["status"] = "drained"
                        self.journal.append(journal_mod.DRAIN, id=sid)
                        self.counters["sweeps_drained"] += 1
                        self._running = None
                    return
                if optimistic:
                    fleet.run_optimistic(max_rounds=1)
                else:
                    fleet.run(max_dispatches=1)
                slices += 1
                self._publish_progress(sid, fleet)
                if slices % self.opts.checkpoint_every_dispatches == 0:
                    save_fleet(fleet, ckpt_dir)
            save_fleet(fleet, ckpt_dir)
            self._settle(sid, fleet, time.monotonic() - t0)
        except BackendLost:
            # the supervision plane already drained the fleet to its
            # checkpoint (save_fleet BEFORE requeueing the lanes, so the
            # slices survive — re-saving here would overwrite them with
            # sliceless QUEUED rows); hand the sweep back FIFO
            with self._lock:
                s["status"] = "queued"
                self.journal.append(
                    journal_mod.REQUEUE, id=sid, reason="backend_lost"
                )
                self.counters["sweeps_requeued"] += 1
                self._queue.insert(0, sid)
                self._running = None
        except (FleetError, SweepError, CheckpointError, ValueError) as e:
            with self._lock:
                s["status"] = "failed"
                s["results"] = {"error": str(e)}
                self.journal.append(
                    journal_mod.COMPLETE, id=sid, ok=False,
                    results={"error": str(e)},
                )
                self.counters["sweeps_failed"] += 1
                self._running = None
            self._dump_metrics()
        finally:
            with self._lock:
                self._running_est_bytes = 0

    def _settle(self, sid: str, fleet, wall_s: float) -> None:
        rows = fleet.results()
        stats = fleet.fleet_stats()
        stats["wall_s"] = round(wall_s, 3)
        stats["resilience"] = fleet.resilience_stats()
        ok = fleet.ok()
        with self._lock:
            s = self.sweeps[sid]
            s["status"] = "done" if ok else "failed"
            s["results"] = rows
            s["stats"] = stats
            self.journal.append(
                journal_mod.COMPLETE, id=sid, ok=ok, results=rows,
                stats=stats,
            )
            self.counters["sweeps_completed" if ok else "sweeps_failed"] += 1
            self.counters["jobs_completed"] += stats["jobs_done"]
            self.counters["kernel_traces"] += fleet.kernel_traces
            self._avg_sweep_wall_s = (
                (1 - _EWMA_ALPHA) * self._avg_sweep_wall_s
                + _EWMA_ALPHA * max(wall_s, 0.001)
            )
            self._running = None
        self._dump_metrics()

    def _settle_from_manifest(self, sid: str, manifest: dict) -> None:
        """Every job in the re-attached manifest is already terminal
        (the previous incarnation died after its final save, before the
        COMPLETE record): settle from the recorded summaries."""
        rows = [e["summary"] for e in manifest["jobs"]]
        ok = all(r["status"] == "done" for r in rows)
        with self._lock:
            s = self.sweeps[sid]
            s["status"] = "done" if ok else "failed"
            s["results"] = rows
            s["stats"] = manifest.get("stats")
            self.journal.append(
                journal_mod.COMPLETE, id=sid, ok=ok, results=rows,
                stats=manifest.get("stats"),
            )
            self.counters["sweeps_completed" if ok else "sweeps_failed"] += 1
            self._running = None
        self._dump_metrics()

    def _worker(self) -> None:
        while not self._draining.is_set():
            with self._lock:
                sid = self._queue.pop(0) if self._queue else None
                if sid is None:
                    self._wake.wait(timeout=0.25)
                    continue
            self._run_sweep(sid)

    # ------------------------------------------------------------------
    # the API server (background thread, unix socket)
    # ------------------------------------------------------------------

    def _make_server(self):
        daemon = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            # unix sockets have no peer (host, port) pair
            def address_string(self):  # pragma: no cover - logging only
                return "unix"

            def log_message(self, *a):  # quiet by default
                pass

            def _reply(self, code: int, body: dict,
                       headers: dict | None = None) -> None:
                blob = (json.dumps(body) + "\n").encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(blob)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(blob)

            def do_GET(self):
                t0 = time.perf_counter()
                try:
                    self._route_get()
                finally:
                    daemon._observe_request(time.perf_counter() - t0)

            def _route_get(self):
                if self.path == "/healthz":
                    return self._reply(200, daemon.health())
                if self.path == "/metricz":
                    return self._reply(200, daemon.metrics_doc())
                if self.path == "/timez":
                    return self._reply(200, daemon.timez_doc())
                if self.path == "/v1/sweeps":
                    return self._reply(200, {"sweeps": daemon.sweep_list()})
                if self.path == "/v1/journal":
                    return self._reply(200, daemon.journal_doc())
                if self.path.startswith("/v1/sweeps/"):
                    sid = self.path.rsplit("/", 1)[-1]
                    info = daemon.sweep_info(sid)
                    if info is None:
                        return self._reply(
                            404, {"error": f"no sweep {sid!r}"}
                        )
                    info.pop("doc", None)  # results, not the input blob
                    return self._reply(200, info)
                return self._reply(404, {"error": "unknown path"})

            def do_POST(self):
                t0 = time.perf_counter()
                try:
                    self._route_post()
                finally:
                    daemon._observe_request(time.perf_counter() - t0)

            def _route_post(self):
                n = int(self.headers.get("Content-Length") or 0)
                raw = self.rfile.read(n) if n else b"{}"
                try:
                    payload = json.loads(raw.decode() or "{}")
                except (UnicodeDecodeError, json.JSONDecodeError):
                    return self._reply(400, {"error": "body is not JSON"})
                if self.path == "/v1/drain":
                    daemon.drain()
                    return self._reply(200, {"draining": True})
                if (self.path.startswith("/v1/sweeps/")
                        and self.path.endswith("/release")):
                    sid = self.path.rsplit("/", 2)[-2]
                    out = daemon.release_sweep(
                        sid, to_peer=str(payload.get("to_peer", "?"))
                    )
                    if out is None:
                        return self._reply(
                            404, {"error": f"no sweep {sid!r}"}
                        )
                    if "busy" in out:
                        # running/settled sweeps never leave their box
                        return self._reply(409, out)
                    return self._reply(200, out)
                if self.path == "/v1/sweeps":
                    doc = payload.get("sweep")
                    if not isinstance(doc, dict):
                        return self._reply(
                            400,
                            {"error": "payload needs a `sweep` document"},
                        )
                    try:
                        origin = payload.get("origin")
                        out = daemon.submit(
                            doc,
                            tenant=str(payload.get("tenant", "default")),
                            backend_faults=payload.get("backend_faults"),
                            origin=(
                                str(origin) if origin is not None else None
                            ),
                        )
                    except ServeError as e:
                        return self._reply(400, {"error": str(e)})
                    if "shed" in out:
                        return self._reply(
                            429, out,
                            headers={
                                "Retry-After": str(out["retry_after_s"]),
                            },
                        )
                    return self._reply(200, out)
                return self._reply(404, {"error": "unknown path"})

        class Server(socketserver.ThreadingMixIn,
                     socketserver.UnixStreamServer):
            daemon_threads = True
            allow_reuse_address = True

        sock = self.opts.socket_path
        os.makedirs(os.path.dirname(os.path.abspath(sock)), exist_ok=True)
        if os.path.exists(sock):
            os.unlink(sock)  # stale socket from a killed incarnation
        return Server(sock, Handler)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def serve_forever(self, install_signals: bool = True) -> int:
        """Run until drained (SIGTERM / POST /v1/drain). Returns 0 on a
        graceful exit; the journal records how far every sweep got."""
        from shadow_tpu.serve.kcache import enable_xla_cache

        # AOT entries skip Python re-traces; the XLA persistent cache
        # (same root unless $JAX_COMPILATION_CACHE_DIR places it, shared
        # with bench.py) skips the StableHLO→binary
        # compile of a deserialized artifact — together a warm restart
        # redispatches in milliseconds
        enable_xla_cache(self.opts.cache_dir)
        self._server = self._make_server()
        if install_signals:
            signal.signal(signal.SIGTERM, lambda *_: self.drain())
            signal.signal(signal.SIGINT, lambda *_: self.drain())
        th = threading.Thread(
            target=self._server.serve_forever, daemon=True
        )
        th.start()
        self._started.set()
        print(
            f"serve: listening on {self.opts.socket_path} "
            f"(state {self.opts.state_dir}, "
            f"{len(self._queue)} sweep(s) replayed into the queue)",
            flush=True,
        )
        try:
            self._worker()
        finally:
            self._server.shutdown()
            self._server.server_close()
            try:
                os.unlink(self.opts.socket_path)
            except OSError:
                pass
            self._dump_metrics()
            self.journal.close()
        print("serve: drained, exiting", flush=True)
        return 0
