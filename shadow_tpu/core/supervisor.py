"""Backend supervision: survive accelerator loss instead of aborting.

Three straight bench rounds (BENCH_r03-r05) died to `backend_unavailable`:
a flaky device at probe time aborted the whole run, and a device lost
MID-run lost everything since the last manual checkpoint. This module
converts every driver's hard-abort path into a supervised state machine:

    HEALTHY ──transient error──▶ RETRY (bounded, jittered exp. backoff)
       │                            │ retries exhausted
       │ deadline misses ≥ limit    ▼
       ├──────────▶ SUSPECT ──probe fails──▶ LOST
       │                └─probe ok─▶ HEALTHY
       │ RESOURCE_EXHAUSTED (XLA OOM / PoolExhausted)
       ├──────────▶ PRESSURE ──ladder rung taken──▶ retry dispatch
       │                └─ladder exhausted─▶ LOST (drain + policy);
       │                  the degradation ladder is core/pressure.py:
       │                  forced downshift → spill escalation → fleet
       │                  lane eviction (docs/fault_tolerance.md §5)
       ▼ classified backend loss
      LOST ──▶ DRAIN (flush state to a crash-consistent checkpoint,
       │        audit chain + drain-reason metadata riding the header)
       ▼
     policy `wait`  re-probe loop (jittered backoff) until the backend
                    answers, rebind the compiled kernels, re-dispatch —
                    hot resume, nothing lost;
     policy `cpu`   degraded-mode failover: move state to the CPU
                    backend, re-lower the window kernels there, keep the
                    simulation advancing; opportunistic probes upshift
                    back to the primary when it recovers;
     policy `abort` raise BackendLost AFTER the drain checkpoint — the
                    run dies but `--resume` finishes it bit-exactly;
     policy `relayout`
                    chip-scoped elastic recovery for multi-chip meshes:
                    after the drain, raise ChipLost carrying the dead
                    chip set — the elastic runner (parallel/elastic.py)
                    rebuilds the mesh over the surviving chips, resumes
                    via checkpoint.restore_relayout (audit chain
                    extended exactly), and relayouts back up when the
                    lost chips answer probes again.

Every dispatch goes through `BackendSupervisor.call(label, thunk)`. The
thunk re-reads the driver's bound kernels on each attempt, so a recovery
that rebinds (`sim._rebind_kernels()`) is picked up transparently; the
window step is a pure function of (state, params, window), so
re-executing an interrupted dispatch is always safe.

The deadline watchdog mirrors the bounded-lag stall detection of the
asynchronous conservative protocol (cs/0409032, PAPERS.md): a dispatch
that falls behind its deadline is a SIGNAL to act on (count it, probe the
backend after `stall_limit` consecutive misses), not something to hang
on. Watchdog jitter only perturbs wall-clock scheduling — simulation
results stay bit-identical because recovery replays pure functions, and
the audit digest chain (obs/audit.py) proves it.

Deterministic testing on CPU rides the fault plane (shadow_tpu/faults):
`kill_backend` / `stall_backend` injections fire at handoff boundaries
and drive this state machine without any real device dying
(tests/test_resilience.py chaos matrix, bench.py --resilience-smoke).
"""

from __future__ import annotations

import random
import time

from shadow_tpu.core.pressure import PoolExhausted

# ---------------------------------------------------------------------------
# failure classification
# ---------------------------------------------------------------------------

TRANSIENT = "transient"
BACKEND_LOST = "backend_lost"
RESOURCE_EXHAUSTED = "resource_exhausted"
FATAL = "fatal"

# Substrings (lowercased) that mark a dispatch error as a dead/unreachable
# backend: the PJRT client's UNAVAILABLE family, dropped connections, and
# the runtime watchdog's own verdicts. Deliberately conservative — an
# unrecognized error stays FATAL and propagates (misclassifying a real bug
# as backend loss would send the supervisor into a pointless drain loop).
_LOST_MARKERS = (
    "unavailable",
    "backend_unavailable",
    "failed to connect",
    "connection reset",
    "connection refused",
    "socket closed",
    "broken pipe",
    "device lost",
    "core halted",
    "tpu driver",
    "worker exited",
    "heartbeat timeout",
)

# A chip held by another process: on a locally attached chip the backend
# cannot start ("Unable to initialize backend 'tpu'", libtpu's "device or
# resource busy" on its lock file). No wait brings it back while that
# process lives, so these fail fast (FATAL), ahead of every other table —
# the same text often carries an UNAVAILABLE that would read as a loss.
_HELD_MARKERS = (
    "device or resource busy",
    "initialize backend",
    "already in use",
)

# Mesh-collective failure markers: a cross-chip collective (the async
# driver's ppermute frontier exchange, the event-exchange all_to_all, a
# pmin all-reduce) died because ONE participant chip is gone, not the
# whole device set. Checked BEFORE the transient table — several runtimes
# phrase these as "ABORTED: collective ..." and a bounded retry would
# spin forever against the same dead peer — and classified BACKEND_LOST
# (chip-scoped: `chip_scoped` reports which family matched) so the drain
# + policy machinery runs with the surviving chips still healthy.
_CHIP_MARKERS = (
    "ppermute",
    "collective-permute",
    "collective_permute",
    "all-reduce",
    "all_reduce",
    "allreduce",
    "all-gather",
    "all_gather",
    "all-to-all",
    "all_to_all",
    "collective operation",
    "collective timeout",
    "peer failure",
    "peer unreachable",
    "remote device",
    "ici link",
    "dcn link",
    "nccl",
    "participant failed",
)

# Errors worth a bounded in-place retry before escalating: interrupted
# collectives and queue hiccups that a healthy backend shakes off.
_TRANSIENT_MARKERS = (
    "aborted",
    "cancelled",
    "temporarily",
    "try again",
    "retry",
)

# XLA memory-pressure markers: the allocator could not place the dispatch's
# working set. NOT transient (an identical retry re-OOMs identically) and
# NOT a loss (the backend is alive) — the pressure ladder (core/pressure.py)
# reshapes the working set, then the dispatch retries.
_EXHAUSTED_MARKERS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "failed to allocate",
    "allocation failure",
    "hbm oom",
)


class BackendLost(RuntimeError):
    """The accelerator backend is gone and the active policy cannot (or
    chose not to) recover in-process. The drain checkpoint — when a
    checkpoint directory is configured — was written before this raise."""


class ChipLost(BackendLost):
    """CHIP-SCOPED backend loss under policy `relayout`: one (or a few)
    chips of a multi-chip mesh died, the surviving chips are healthy,
    and the drain checkpoint was written. `chips` is the frozenset of
    lost chip indices (mesh device order); `path` the drain checkpoint
    (None when no checkpoint directory is configured). The elastic
    runner (parallel/elastic.py) catches this, rebuilds the mesh over
    the survivors, and resumes via checkpoint.restore_relayout."""

    def __init__(self, message: str, *, chips=frozenset(),
                 path: str | None = None):
        super().__init__(message)
        self.chips = frozenset(int(c) for c in chips)
        self.path = path


def classify_failure(exc: BaseException) -> str:
    """TRANSIENT (bounded retry), RESOURCE_EXHAUSTED (pressure ladder),
    BACKEND_LOST (drain + policy), or FATAL (re-raise: a real bug, or a
    chip another process holds). Mesh-collective failures (`chip_scoped`)
    classify BACKEND_LOST — checked before the transient table, so a
    dead ppermute peer is never retried forever."""
    if isinstance(exc, BackendLost):
        return BACKEND_LOST
    if isinstance(exc, PoolExhausted):
        return RESOURCE_EXHAUSTED
    msg = f"{type(exc).__name__}: {exc}".lower()
    if any(marker in msg for marker in _HELD_MARKERS):
        return FATAL
    for marker in _EXHAUSTED_MARKERS:
        if marker in msg:
            return RESOURCE_EXHAUSTED
    for marker in _CHIP_MARKERS:
        if marker in msg:
            return BACKEND_LOST
    for marker in _TRANSIENT_MARKERS:
        if marker in msg:
            return TRANSIENT
    for marker in _LOST_MARKERS:
        if marker in msg:
            return BACKEND_LOST
    return FATAL


def chip_scoped(exc: BaseException) -> bool:
    """True when `exc` names a mesh-collective failure — loss of ONE
    participant chip, not the whole device set. The relayout policy uses
    this (plus the kill_chip injection's explicit chip set, plus a
    MeshHealth probe sweep) to decide that degrading to the surviving
    mesh is sound where a whole-backend loss would not be."""
    if isinstance(exc, ChipLost):
        return True
    msg = f"{type(exc).__name__}: {exc}".lower()
    return any(marker in msg for marker in _CHIP_MARKERS)


def probe_backend() -> bool:
    """One trivial dispatch against the default backend — the shared
    liveness probe: the supervisor's SUSPECT→LOST check and the serve
    daemon's `/healthz` both use it, so a probe verdict means the same
    thing everywhere. In-process on purpose: a child process could not
    reach a chip this process holds."""
    try:
        import jax.numpy as jnp

        jnp.zeros((), jnp.int32).block_until_ready()
        return True
    except Exception:
        return False


_default_probe = probe_backend  # supervisor-internal historical name


# ---------------------------------------------------------------------------
# peer probe state: the SUSPECT→LOST ladder as reusable data
# ---------------------------------------------------------------------------

PEER_HEALTHY = "healthy"
PEER_SUSPECT = "suspect"
PEER_LOST = "lost"

PEER_STATES = (PEER_HEALTHY, PEER_SUSPECT, PEER_LOST)


class ProbeLadder:
    """The bounded-miss health ladder of the supervisor state machine
    (HEALTHY → SUSPECT → LOST, cs/0409032's bounded-lag signal) packaged
    as standalone peer-probe state: the serve federation's router
    (serve/federation.py) runs one ladder per serve daemon, exactly the
    classification discipline BackendSupervisor applies per backend —
    a single missed probe is a SIGNAL (SUSPECT), `lost_after`
    consecutive misses a verdict (LOST), and any success snaps the
    ladder back to HEALTHY (recovery is instant, loss is earned).

    `backoff_s()` is the jittered exponential wait before the NEXT
    probe of a non-healthy peer — the same ±50% decorrelation jitter
    the supervisor applies to its re-probe loop, seeded so tests are
    deterministic. Wall scheduling only; never simulation results.
    """

    def __init__(self, *, lost_after: int = 3,
                 backoff_base_s: float = 0.25,
                 backoff_cap_s: float = 5.0, seed: int = 0):
        self.lost_after = max(1, int(lost_after))
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self._rng = random.Random(seed)
        self.misses = 0
        self.probes = 0
        self.state = PEER_HEALTHY

    def record(self, ok: bool) -> str:
        """Fold one probe verdict; returns the post-probe state."""
        self.probes += 1
        if ok:
            self.misses = 0
            self.state = PEER_HEALTHY
        else:
            self.misses += 1
            self.state = (
                PEER_LOST if self.misses >= self.lost_after
                else PEER_SUSPECT
            )
        return self.state

    def backoff_s(self) -> float:
        """Jittered exponential wait before the next probe, keyed to the
        consecutive-miss count (0 misses → 0: healthy peers are probed
        on the caller's regular cadence)."""
        if self.misses == 0:
            return 0.0
        base = min(
            self.backoff_base_s * (2 ** (self.misses - 1)),
            self.backoff_cap_s,
        )
        return base * (0.5 + self._rng.random())


class PendingDispatch:
    """One device dispatch split into its two halves (the pipelined
    drivers' seam, core/pipeline.py):

      issue_fn()     enqueue the dispatch — returns device FUTURES (jax's
                     async dispatch), never blocks on results;
      fetch_fn(out)  the blocking host reads of those futures (the
                     `int()` / `device_get` scalar fetches).

    `BackendSupervisor.issue` launches the issue half immediately (when
    the backend is believed healthy) and hands back this ticket;
    `await_result` runs the fetch half under the full classified-retry +
    watchdog state machine — a retry re-runs BOTH halves, so recovery
    rebinds and per-attempt clamps behave exactly like the fused
    `call()` thunk did. A ticket also works unsupervised (the drivers'
    zero-overhead default): `direct()` + `await_direct()` reproduce a
    plain thunk call with errors propagating raw."""

    __slots__ = (
        "label", "issue_fn", "fetch_fn", "_out", "_error", "_t0",
        "_live",
    )

    def __init__(self, label: str, issue_fn, fetch_fn):
        self.label = label
        self.issue_fn = issue_fn
        self.fetch_fn = fetch_fn
        self._out = None
        self._error = None
        self._t0 = None
        self._live = False

    @classmethod
    def direct(cls, label: str, issue_fn, fetch_fn) -> "PendingDispatch":
        """Unsupervised ticket: issue now, fetch at await_direct."""
        p = cls(label, issue_fn, fetch_fn)
        p.launch(time.monotonic)
        return p

    def launch(self, clock) -> None:
        """Run the issue half now. An issue-time error (a tracing bug, an
        immediately-failing enqueue) is captured and re-raised inside the
        awaiter's classified try — never lost, never early."""
        self._t0 = clock()
        try:
            self._out = self.issue_fn()
            self._live = True
        except Exception as exc:  # noqa: BLE001 — classified at await
            self._error = exc
            self._live = True

    def claim(self):
        """Surrender the issued-ahead attempt ONCE: (t0, out, error), or
        None when nothing was launched (or it was already claimed /
        abandoned) — the awaiter then re-issues fresh."""
        if not self._live:
            return None
        self._live = False
        out, err = self._out, self._error
        self._out = self._error = None
        return (self._t0, out, err)

    def abandon(self) -> None:
        """Drop the issued futures without fetching (a pipelined driver
        discarding a speculative dispatch whose inputs a handoff
        invalidated). The device work is wasted, never observed; jax
        garbage-collects the result buffers."""
        self._live = False
        self._out = self._error = None

    def await_direct(self):
        """The unsupervised await half: fetch the issued futures (or
        re-run the halves if never launched); errors propagate raw —
        exactly a bare thunk call."""
        c = self.claim()
        if c is None:
            return self.fetch_fn(self.issue_fn())
        if c[2] is not None:
            raise c[2]
        return self.fetch_fn(c[1])

# _chips_down sentinel for probe-discovered (not injection-driven) dead
# chips: probing one consults the MeshHealth device prober, never an
# injection countdown
_REAL_CHIP = -1


class BackendSupervisor:
    """Wraps device dispatches in a deadline watchdog with classified
    failure handling. One per run; bind to the driving Simulation /
    IslandSimulation / FleetSimulation with ``sim.attach_supervisor``.

    The bound sim must duck-type four recovery hooks:
      _drain_to_checkpoint(reason, ckpt_dir=None)  flush state + metadata
      _rebind_kernels()                            fresh compiled kernels
      _enter_cpu_failover() / _exit_cpu_failover() degraded-mode swap

    ``sleep`` / ``clock`` are injectable for tests (wall scheduling only —
    never simulation results).
    """

    POLICIES = ("wait", "cpu", "abort", "relayout")

    def __init__(
        self,
        policy: str = "abort",
        *,
        max_retries: int = 3,
        backoff_base_s: float = 0.05,
        backoff_cap_s: float = 5.0,
        dispatch_deadline_s: float = 300.0,
        stall_limit: int = 3,
        probe_budget_s: float = 900.0,
        probe_interval_s: float = 5.0,
        probe_interval_cap_s: float = 60.0,
        recheck_every: int = 8,
        max_drains: int = 16,
        drain_dir: str | None = None,
        probe_fn=None,
        seed: int = 0,
        sleep=time.sleep,
        clock=time.monotonic,
    ):
        if policy not in self.POLICIES:
            raise ValueError(
                f"on_backend_loss policy must be one of {self.POLICIES}, "
                f"got {policy!r}"
            )
        self.policy = policy
        self.max_retries = int(max_retries)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.dispatch_deadline_s = float(dispatch_deadline_s)
        self.stall_limit = max(1, int(stall_limit))
        self.probe_budget_s = float(probe_budget_s)
        self.probe_interval_s = float(probe_interval_s)
        self.probe_interval_cap_s = float(probe_interval_cap_s)
        self.recheck_every = max(1, int(recheck_every))
        self.max_drains = int(max_drains)
        self.drain_dir = drain_dir
        self._probe_fn = probe_fn or _default_probe
        # jitter decorrelates probe herds across a fleet of runs; wall
        # scheduling only — simulation results never depend on it
        self._rng = random.Random(seed)
        self._sleep = sleep
        self._clock = clock
        self._sim = None
        self._dead = False
        self.failover = False  # running on the CPU fallback backend
        self._consec_stalls = 0
        self._since_recheck = 0
        self._down_since: float | None = None
        # injected faults (shadow_tpu/faults kill_backend / stall_backend /
        # exhaust_backend): None = no kill injection armed; an int counts
        # FAILED probes until the simulated backend answers again (-1 =
        # never recovers). _inject_exhausts counts dispatch attempts that
        # fail with a simulated XLA RESOURCE_EXHAUSTED before the
        # allocation "fits" again (the pressure ladder's reshapes are what
        # make the retries converge).
        self._inject_probes_left: int | None = None
        self._inject_stalls = 0
        self._inject_exhausts = 0
        # chip-scoped loss bookkeeping (policy `relayout`, and per-chip
        # probing under `wait`): chip index -> remaining FAILED probes
        # before the simulated chip answers again (None = stays down).
        # Real (non-injected) chips are probed through the bound
        # MeshHealth prober (parallel/mesh.py) when one is attached.
        self._chips_down: dict[int, int | None] = {}
        self.mesh_health = None
        self.counters = {
            "dispatches": 0,
            "retries": 0,
            "backoffs": 0,
            "stalls": 0,
            "probes": 0,
            "backend_losses": 0,
            "exhaustions": 0,
            "pressure_steps": 0,
            "drains": 0,
            "failovers": 0,
            "failbacks": 0,
            "hot_resumes": 0,
            "downtime_ns": 0,
            "chip_losses": 0,
        }

    # -- binding + fault-plane injection hooks --

    def bind(self, sim) -> None:
        self._sim = sim

    def attach_mesh_health(self, health) -> None:
        """Bind a per-chip prober (parallel/mesh.MeshHealth): chip
        probes that are not injection-driven dispatch against the
        individual device instead of the default backend."""
        self.mesh_health = health

    def inject_kill_chip(self, chip: int,
                         recover_after: int | None = None) -> None:
        """Simulate the loss of ONE mesh chip (the `kill_chip` fault
        op): the next supervised dispatch fails chip-scoped — under
        policy `relayout` the drain is followed by a ChipLost carrying
        the dead chip set (the elastic runner's rebuild signal); under
        `wait` the probe loop holds until every down chip answers.
        Probes of this chip fail `recover_after` times before the
        simulated chip recovers (None = stays down)."""
        self._dead = True
        self.counters["backend_losses"] += 1
        self.counters["chip_losses"] += 1
        self._chips_down[int(chip)] = (
            None if recover_after is None else max(0, int(recover_after))
        )

    def inject_kill(self, recover_after: int | None = None) -> None:
        """Simulate backend loss (the `kill_backend` fault op): the next
        supervised dispatch drains; probes fail `recover_after` times
        before the backend "answers" again (None = stays down)."""
        self._dead = True
        self.counters["backend_losses"] += 1
        self._inject_probes_left = (
            -1 if recover_after is None else max(0, int(recover_after))
        )

    def inject_stall(self, count: int = 1) -> None:
        """Simulate `count` dispatches missing the deadline (the
        `stall_backend` fault op) — exercises the stall→probe ladder
        without any real slowness."""
        self._inject_stalls += max(1, int(count))

    def inject_exhaust(self, recover_after: int | None = 1) -> None:
        """Simulate XLA memory exhaustion (the `exhaust_backend` fault
        op): the next `recover_after` supervised dispatch attempts fail
        with a classified RESOURCE_EXHAUSTED — each failure runs one
        pressure-ladder rung (core/pressure.py), modeling an allocation
        that fits only after the ladder reshaped the working set."""
        self._inject_exhausts += max(1, int(recover_after or 1))

    @property
    def degraded(self) -> bool:
        """True while the backend is lost or the run is on the CPU
        fallback — the interlock signal elective reshapes (the shard
        balancer's live migrations, parallel/balancer.py) consult: no
        optional work while survival machinery is driving."""
        return self._dead or self.failover

    # -- probing --

    def probe(self) -> bool:
        self.counters["probes"] += 1
        if self._chips_down:
            # chip-scoped outage: the backend answers when every down
            # chip does (the `wait` policy's hold-until-whole condition)
            for chip in sorted(self._chips_down):
                self._probe_chip_raw(chip)
            return not self._chips_down
        if self._inject_probes_left is not None:
            if self._inject_probes_left == 0:
                self._inject_probes_left = None  # simulated recovery
                return True
            if self._inject_probes_left > 0:
                self._inject_probes_left -= 1
            return False
        return bool(self._probe_fn())

    def probe_chip(self, chip: int) -> bool:
        """Probe ONE mesh chip — the elastic re-expansion loop's signal
        (parallel/elastic.py polls lost chips through this and relayouts
        back up after a hysteresis streak of successes)."""
        self.counters["probes"] += 1
        return self._probe_chip_raw(int(chip))

    def _probe_chip_raw(self, chip: int) -> bool:
        if chip in self._chips_down:
            left = self._chips_down[chip]
            if left == _REAL_CHIP:
                # probe-discovered (not injected) dead chip: ask the
                # actual device through the MeshHealth prober
                if self.mesh_health is not None and bool(
                    self.mesh_health.probe_chip(chip)
                ):
                    del self._chips_down[chip]
                    return True
                return False
            if left is None:
                return False
            if left <= 0:
                del self._chips_down[chip]  # simulated chip recovery
                return True
            self._chips_down[chip] = left - 1
            return False
        if self.mesh_health is not None:
            return bool(self.mesh_health.probe_chip(chip))
        return bool(self._probe_fn())

    @property
    def chips_down(self) -> frozenset[int]:
        """The currently-known dead chip set (injected or probe-found)."""
        return frozenset(self._chips_down)

    def mark_chip_down(self, chip: int) -> None:
        """Record a probe-discovered dead chip (MeshHealth sweep, real
        hardware path): subsequent probes go to the device itself."""
        self._chips_down.setdefault(int(chip), _REAL_CHIP)

    # -- the supervised dispatch --

    def call(self, label: str, thunk):
        """Run one device dispatch to completion under supervision.

        `thunk` takes no arguments, performs the dispatch INCLUDING the
        blocking host fetches (so async-dispatch errors surface here, not
        at a later unsupervised sync), and must re-read the driver's
        bound kernel attributes — recovery rebinds them.

        Implemented as issue()+await_result() with the whole thunk as the
        issue half — the fused form every pre-pipeline call site keeps.
        """
        return self.await_result(self.issue(label, thunk, lambda out: out))

    @property
    def pending_disruption(self) -> bool:
        """True when the NEXT supervised dispatch will not run clean: the
        backend is (injected-)dead, the run is on the CPU fallback, or an
        injected exhaust/stall is armed. The pipelined drivers consult
        this instead of issuing ahead — a speculative dispatch against a
        known disruption would only be discarded (and, for injections,
        would reorder the fault against the serial schedule)."""
        return (
            self._dead or self.failover or self._inject_exhausts > 0
            or self._inject_stalls > 0
        )

    def issue(self, label: str, issue_fn, fetch_fn) -> PendingDispatch:
        """The ISSUE half of a supervised dispatch: enqueue the device
        work asynchronously (jax dispatch returns futures) and hand back
        the ticket. Nothing blocks, nothing is classified yet — the full
        retry ladder, pressure rungs, watchdog, and loss policies all run
        in await_result, operating on the awaited half. When the backend
        is already known-disrupted the launch is skipped; await_result
        then recovers first and issues fresh, exactly like call() did."""
        p = PendingDispatch(label, issue_fn, fetch_fn)
        if not self.pending_disruption:
            p.launch(self._clock)
        return p

    def await_result(self, p: PendingDispatch):
        """The AWAIT half: block on the issued dispatch's host fetches
        under the classified state machine. First pass consumes the
        issued-ahead futures (deadline measured from their issue time);
        any retry re-runs BOTH halves — issue_fn re-reads the bound
        kernels and re-clamps, so recovery and mid-dispatch pressure
        rungs are picked up exactly as under the fused call()."""
        label = p.label
        retries = 0
        while True:
            if self._dead:
                p.abandon()
                self._recover(label)  # raises under policy `abort`
            if self.failover:
                self._maybe_failback()
            self.counters["dispatches"] += 1
            pre = p.claim()
            t0 = pre[0] if pre is not None else self._clock()
            try:
                if self._inject_exhausts > 0:
                    self._inject_exhausts -= 1
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: out of memory allocating "
                        "window buffers (injected exhaust_backend)"
                    )
                if pre is not None:
                    if pre[2] is not None:
                        raise pre[2]
                    out = p.fetch_fn(pre[1])
                else:
                    out = p.fetch_fn(p.issue_fn())
            except Exception as exc:  # noqa: BLE001 — classified below
                kind = classify_failure(exc)
                if kind == TRANSIENT and retries < self.max_retries:
                    retries += 1
                    self.counters["retries"] += 1
                    self._backoff(retries)
                    continue
                if kind == FATAL:
                    raise
                if kind == RESOURCE_EXHAUSTED:
                    # memory pressure, not loss: the backend is alive but
                    # the working set does not fit. Run one degradation-
                    # ladder rung (core/pressure.py) and retry — the
                    # thunk re-reads the bound kernels, so a downshift's
                    # rebind is picked up transparently.
                    self.counters["exhaustions"] += 1
                    if self._pressure_step(label, exc):
                        continue
                    # ladder exhausted/unavailable: treat as a loss —
                    # drain to a checkpoint, then the configured policy
                # backend loss, or transient retries exhausted (a backend
                # that cannot absorb a bounded retry burst is not healthy)
                self._dead = True
                self.counters["backend_losses"] += 1
                if chip_scoped(exc) and not self._chips_down:
                    # a mesh collective died against one peer: find the
                    # dead participant(s) so the relayout policy can
                    # degrade to the survivors instead of declaring the
                    # whole device set gone
                    self.counters["chip_losses"] += 1
                    self._sweep_chips()
                self._note_down()
                continue
            elapsed = self._clock() - t0
            if self._inject_stalls > 0:
                self._inject_stalls -= 1
                elapsed = self.dispatch_deadline_s + elapsed
            if elapsed > self.dispatch_deadline_s:
                # bounded-lag signal (cs/0409032): a deadline miss is a
                # signal to act on, not to hang on — the result is valid
                # (the dispatch DID complete), but consecutive misses
                # trigger a probe, and a failed probe declares the
                # backend lost before the next dispatch wedges forever.
                self.counters["stalls"] += 1
                self._consec_stalls += 1
                if self._consec_stalls >= self.stall_limit:
                    self._consec_stalls = 0
                    if not self.probe():
                        self._dead = True
                        self.counters["backend_losses"] += 1
                        self._note_down()
                        continue
            else:
                self._consec_stalls = 0
            return out

    def _pressure_step(self, label: str, exc: BaseException) -> bool:
        """One memory-ladder rung via the bound sim's pressure plane;
        False when no sim is bound or its ladder is exhausted (the
        caller then escalates to the drain + loss-policy path)."""
        sim = self._sim
        step = getattr(sim, "_pressure_ladder_step", None)
        if step is None:
            return False
        if step(f"{label}: {exc}"):
            self.counters["pressure_steps"] += 1
            return True
        return False

    # -- loss handling: drain, then the configured policy --

    def _recover(self, label: str) -> None:
        sim = self._sim
        if sim is None:
            raise BackendLost(
                f"backend lost at dispatch {label!r} with no bound sim "
                f"(attach_supervisor first)"
            )
        self._note_down()
        if self.counters["drains"] >= self.max_drains:
            raise BackendLost(
                f"backend lost {self.counters['drains']} times; giving up "
                f"(max_drains={self.max_drains})"
            )
        self.counters["drains"] += 1
        path = sim._drain_to_checkpoint(
            f"backend_lost:{label}", ckpt_dir=self.drain_dir
        )
        if self.policy == "abort":
            note = f"; drained to {path}" if path else ""
            raise BackendLost(
                f"backend lost at dispatch {label!r} "
                f"(policy abort{note}; resume with --resume)"
            )
        if self.policy == "relayout":
            # chip-scoped elastic recovery: the drain checkpoint is on
            # disk; hand the dead chip set to the elastic runner
            # (parallel/elastic.py), which rebuilds the mesh over the
            # survivors and resumes via checkpoint.restore_relayout.
            # The survivors are healthy — clear the dead flag so the
            # re-bound supervisor serves the degraded mesh immediately;
            # the lost chips stay in _chips_down for re-expansion probes.
            chips = frozenset(self._chips_down)
            self._dead = False
            self._note_up()
            raise ChipLost(
                f"chip(s) {sorted(chips) if chips else '?'} lost at "
                f"dispatch {label!r} (policy relayout; drained to {path}); "
                f"relayout onto the surviving mesh and resume",
                chips=chips, path=path,
            )
        if self.policy == "cpu":
            sim._enter_cpu_failover()
            self.failover = True
            self.counters["failovers"] += 1
            self._since_recheck = 0
            self._dead = False
            return
        # policy `wait`: hot resume — re-probe with jittered backoff
        # until the backend returns, then rebind the compiled kernels
        deadline = self._clock() + self.probe_budget_s
        delay = self.probe_interval_s
        while not self.probe():
            if self._clock() >= deadline:
                raise BackendLost(
                    f"backend did not return within the "
                    f"{self.probe_budget_s:.0f}s probe budget at dispatch "
                    f"{label!r} (drained to {path}; resume with --resume)"
                )
            self.counters["backoffs"] += 1
            self._sleep(self._jitter(delay))
            delay = min(delay * 2, self.probe_interval_cap_s)
        sim._rebind_kernels()
        self._dead = False
        self.counters["hot_resumes"] += 1
        self._note_up()

    def _maybe_failback(self) -> None:
        """In CPU failover, opportunistically probe the primary every
        `recheck_every` dispatches; upshift back when it answers."""
        self._since_recheck += 1
        if self._since_recheck < self.recheck_every:
            return
        self._since_recheck = 0
        if self.probe():
            self._sim._exit_cpu_failover()
            self.failover = False
            self.counters["failbacks"] += 1
            self._note_up()

    def _sweep_chips(self) -> None:
        """Probe every mesh chip through the bound MeshHealth prober and
        mark the non-answering ones down. A no-op without a prober (the
        deterministic CPU path gets its chip set from kill_chip
        injections instead)."""
        mh = self.mesh_health
        if mh is None:
            return
        for chip, up in enumerate(mh.probe_all()):
            self.counters["probes"] += 1
            if not up:
                self.mark_chip_down(chip)

    # -- wall bookkeeping --

    def _note_down(self) -> None:
        if self._down_since is None:
            self._down_since = self._clock()

    def _note_up(self) -> None:
        if self._down_since is not None:
            self.counters["downtime_ns"] += int(
                (self._clock() - self._down_since) * 1e9
            )
            self._down_since = None

    def _backoff(self, attempt: int) -> None:
        self.counters["backoffs"] += 1
        delay = min(
            self.backoff_base_s * (2 ** (attempt - 1)), self.backoff_cap_s
        )
        self._sleep(self._jitter(delay))

    def _jitter(self, delay: float) -> float:
        """±50% decorrelation so a fleet of supervisors never probes a
        recovering worker in lockstep."""
        return delay * (0.5 + self._rng.random())

    def stats(self) -> dict:
        """The `resilience.*` metrics namespace (schema v6; v8 adds the
        exhaustions / pressure_steps memory-pressure tallies; v12 adds
        chip_losses — the chip-scoped subset of backend_losses)."""
        d = dict(self.counters)
        d["failover_active"] = int(self.failover)
        return d
