"""Resource-pressure survival plane: degrade deterministically, never die.

Shadow's single worst production failure mode is resource exhaustion —
large runs die to OOM kills and event-queue saturation. The TPU port
inherits both flavors:

  * BACKEND pressure: XLA raises ``RESOURCE_EXHAUSTED`` when a dispatch
    cannot allocate its HBM working set (pool + dense window + sort
    temporaries);
  * POOL pressure: the event pool leaves too little merge headroom for
    even one window's inflow and the drivers stall — which, before this
    module, surfaced as a bare ``RuntimeError`` mid-run.

PR 6/PR 8 made backend *loss* survivable (drain → checkpoint → resume);
this module does the same for backend *pressure*. Both signals feed one
policy-driven degradation ladder executed at dispatch boundaries, where
every action is a host-side reshape of machinery that is already proven
bit-exact (gearbox re-sorts, spill-tier parking, fleet lane swaps), so a
degraded run commits the identical event schedule — the audit digest
chain (obs/audit.py) is the proof instrument:

  memory ladder (XLA ``RESOURCE_EXHAUSTED`` at a supervised dispatch):
    1. forced gear DOWNSHIFT — override the red-zone upshift rule: a
       smaller pool kernel needs less device memory; overflow rows park
       on the host spill tier (order-preserving) instead of the device.
       The gear holds down (``hold_gear``) until pressure clears.
    2. spill-tier ESCALATION — shrink the spill fill mark one notch per
       rung (``fill_shrink``), trading device residency for host memory.
    3. fleet lane EVICTION — requeue the heaviest running job
       (``FleetScheduler.requeue``); the freed lane shrinks the resident
       working set and admission holds until pressure clears.
    4. drain-to-checkpoint + the --on-backend-loss policy (the
       supervisor's existing wait/cpu/abort machinery).

  pool ladder (driver headroom stall):
    1. forced UPSHIFT when a bigger gear exists (and no memory hold
       pins the gear down).
    2. injected-saturation YIELD — ``saturate_pool`` pressure responds
       to the ladder like ``exhaust_backend``'s recover_after contract:
       each rung the spill tier absorbs relieves the simulated external
       pressure one notch (frac doubles toward 1.0).
    3. force one spill EPISODE (the stall may predate any red-zone
       crossing: occupancy under the mark can still leave too little
       merge headroom for a whole window's inflow).
    4. give up: drain-to-checkpoint, then raise the *typed*
       ``PoolExhausted`` (resume with --resume at a reshaped config).

Deterministic testing rides the fault plane (shadow_tpu/faults):
``exhaust_backend {at, recover_after}`` injects classified OOM failures
into supervised dispatches; ``saturate_pool {at, frac}`` scales the
spill marks. Both execute at virtual-time-keyed handoff boundaries, so
the chaos matrix (tests/test_pressure.py, bench.py --pressure-smoke)
asserts post-degradation digest chains bit-identical to uninterrupted
runs on CPU.

This is a HOST module: nothing here is ever traced into a kernel, and
every ladder action happens at a dispatch boundary with the state
synced (shadowlint classifies it host; tests/test_analysis.py pins it).
"""

from __future__ import annotations

import os


class PoolExhausted(RuntimeError):
    """The event pool cannot make progress and the pressure ladder is
    exhausted (or disabled). Carries the stall diagnostics so callers —
    and operators reading the message — know the shape that failed:
    ``window`` (the frozen virtual-time frontier, ns), ``occupancy``
    (live pool rows at the stall) and ``capacity`` (the active gear's
    pool rows). Classified RESOURCE_EXHAUSTED by the supervisor."""

    def __init__(self, message: str, *, window: int | None = None,
                 occupancy: int | None = None,
                 capacity: int | None = None):
        super().__init__(message)
        self.window = window
        self.occupancy = occupancy
        self.capacity = capacity


# ---------------------------------------------------------------------------
# HBM budget estimator
# ---------------------------------------------------------------------------
#
# The window kernel's peak working set is the live state plus the sort
# temporaries: the key sorts and the row gathers that follow them
# materialize a copy of every column, and the dense-window extraction
# concatenates pool + filler rows before sorting, so the transient peak is
# a small multiple of the pool + dense bytes. The factor below is deliberately conservative
# (an over-estimate sheds a sweep the device could maybe have served; an
# under-estimate OOMs it mid-run).

SORT_TEMP_FACTOR = 2

# bytes per event row: time i64 + dst/src/seq/kind i32 + payload i64 cols
_EVENT_FIXED_BYTES = 8 + 4 * 4


def _row_bytes(payload_cols: int) -> int:
    return _EVENT_FIXED_BYTES + 8 * int(payload_cols)


def tree_bytes(tree) -> int:
    """Total array bytes of a pytree (the avals ARE the state leaves)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None:
            total += int(leaf.size) * int(dtype.itemsize)
    return total


def estimate_hbm_bytes(sim, level: int | None = None) -> dict:
    """Estimate the device-memory footprint of `sim` at gear `level`
    (default: the active gear): resident state + params plus the kernel's
    sort/dense temporaries, sized from the state avals.

    Works for Simulation, IslandSimulation and FleetSimulation — the
    leading lane/shard axes are already part of the state leaves' shapes.
    Returns a breakdown dict; ``total_bytes`` is the admission signal.
    """
    ladder = getattr(sim, "_gear_ladder", None) or getattr(sim, "_ladder", None)
    gear = getattr(sim, "_gear", 0)
    spec = None
    if ladder:
        spec = ladder[gear if level is None else level]
    state_b = tree_bytes(sim.state)
    params_b = tree_bytes(getattr(sim, "params", None))
    pool_b = tree_bytes(sim.state.pool)
    # rows per pool = the trailing axis (leading dims are lanes/shards;
    # gear capacities are per-shard, matching)
    cur_rows = int(sim.state.pool.time.shape[-1])
    if spec is not None and cur_rows:
        # rescale the pool component to the target gear's capacity
        pool_at = pool_b * spec.capacity // max(1, cur_rows)
    else:
        pool_at = pool_b
    # dense window matrix: one (K+1)-wide row block per host row (lanes
    # and shards ride the host leaf's leading dims, counted via gid.size)
    host_rows = int(sim.state.host.gid.size)
    K = spec.K if spec is not None else getattr(sim, "K", 32)
    PP = int(sim.state.pool.payload.shape[-1])
    dense_b = host_rows * (K + 1) * _row_bytes(PP)
    temp_b = SORT_TEMP_FACTOR * (pool_at + dense_b)
    total = state_b + params_b + (pool_at - pool_b) + dense_b + temp_b
    return {
        "state_bytes": int(state_b),
        "params_bytes": int(params_b),
        "pool_bytes": int(pool_at),
        "dense_bytes": int(dense_b),
        "temp_bytes": int(temp_b),
        "total_bytes": int(total),
        "gear_level": int(spec.level if spec is not None else gear),
    }


def estimate_config_bytes(cfg, lanes: int = 1) -> int:
    """Preflight footprint of a run described only by its Config — the
    serve daemon's admission estimator (no device state exists yet, so
    this sizes the avals analytically from the kernel-shaping fields):

        lanes x (pool + dense + host block + sort temporaries)

    Deliberately coarse and conservative; documented in docs/serving.md.
    """
    H = sum(int(getattr(h, "quantity", 1)) for h in cfg.hosts)
    exp = cfg.experimental
    C = int(getattr(exp, "event_capacity", 1 << 14))
    K = int(getattr(exp, "events_per_host_per_window", 32))
    O = int(getattr(exp, "outbox_slots", 64))
    B = int(getattr(exp, "inbox_slots", 8))
    PP = 2  # packed payload columns at the default 4 payload words
    row = _row_bytes(PP)
    pool_b = C * row
    dense_b = H * (K + 1) * row
    box_b = H * (O + B) * row
    # per-host SoA block (HostState + net subs): a generous flat estimate
    host_b = H * 256
    per_lane = pool_b + dense_b + box_b + host_b \
        + SORT_TEMP_FACTOR * (pool_b + dense_b)
    return int(max(1, lanes) * per_lane)


def device_memory_budget() -> int | None:
    """The accelerator's usable memory in bytes, or None when unknown
    (CPU backends report no limit — admission is then unbounded).
    ``SHADOW_TPU_HBM_BUDGET`` overrides for tests and capped deployments.
    """
    env = os.environ.get("SHADOW_TPU_HBM_BUDGET")
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            return None
    try:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
    except Exception:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def headroom_bytes(estimated: int, budget: int | None = None) -> int | None:
    """Live headroom gauge: budget − estimate (None when no budget)."""
    if budget is None:
        budget = device_memory_budget()
    if budget is None:
        return None
    return int(budget) - int(estimated)


def overflow_advice(sim, dropped: int) -> tuple[str, dict]:
    """Actionable sizing advice for a run that ended with
    ``pool_overflow_dropped > 0`` (the __main__ end-of-run warning):
    suggest a capacity that would have absorbed the overflow, and gearing
    when the build ran a single fixed tier."""
    ladder = getattr(sim, "_gear_ladder", None) or getattr(sim, "_ladder", None)
    cap = ladder[-1].capacity if ladder else int(sim.state.pool.capacity)
    need = cap + int(dropped) + cap // 2
    suggested = 1
    while suggested < need:
        suggested <<= 1
    advice = {
        "suggested_event_capacity": int(suggested),
        "suggested_pool_gears": max(2, int(getattr(sim, "pool_gears", 1))),
    }
    msg = (
        f"raise experimental.event_capacity to ~{suggested} "
        f"(top tier was {cap})"
    )
    if getattr(sim, "pool_gears", 1) <= 1:
        msg += (
            "; or run with experimental.pool_gears >= 2 so the red-zone "
            "upshift absorbs the burst before the merge drops"
        )
    return msg, advice


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------


class PressurePolicy:
    """Knobs for the degradation ladder (docs/fault_tolerance.md §5)."""

    def __init__(
        self,
        enabled: bool = True,
        allow_downshift: bool = True,
        allow_spill_escalation: bool = True,
        allow_lane_eviction: bool = True,
        max_fill_shrink: int = 3,
        recover_after_dispatches: int = 8,
        eviction_hold_dispatches: int = 4,
    ):
        self.enabled = bool(enabled)
        self.allow_downshift = bool(allow_downshift)
        self.allow_spill_escalation = bool(allow_spill_escalation)
        self.allow_lane_eviction = bool(allow_lane_eviction)
        self.max_fill_shrink = int(max_fill_shrink)
        self.recover_after_dispatches = max(1, int(recover_after_dispatches))
        self.eviction_hold_dispatches = max(1, int(eviction_hold_dispatches))


class PressureController:
    """Per-run ladder state + the ``pressure.*`` metrics namespace
    (schema v8). One per sim, attached lazily by the drivers on the
    first pressure signal (``Simulation._pressure()``) or explicitly via
    ``attach_pressure`` for a custom policy.

    The controller is pure host bookkeeping: the bound sim executes the
    actual reshapes through its ``_pressure_relieve_pool`` /
    ``_pressure_relieve_memory`` hooks, which return the action name
    taken (counted here) or None when their ladder is exhausted.
    Determinism: every action depends only on sim state and dispatch
    counts — never wall time.
    """

    def __init__(self, policy: PressurePolicy | None = None):
        self.policy = policy or PressurePolicy()
        # ladder posture (consulted by Simulation._spill_marks / _gear_tick)
        self.fill_shrink = 0  # spill fill mark halves per notch
        self.saturate_frac: float | None = None  # injected saturation
        self.hold_gear = False  # forced-downshift hold: no upshifts
        self._stall_steps = 0  # rungs taken since the last progress note
        self._clean = 0  # clean dispatches toward relaxation
        self.counters = {
            "pool_exhausted": 0,
            "backend_exhausted": 0,
            "ladder_steps": 0,
            "downshifts": 0,
            "upshifts": 0,
            "spill_escalations": 0,
            "lane_evictions": 0,
            "job_sheds": 0,
            "saturations": 0,
            "saturation_yields": 0,
            "relaxations": 0,
            "gave_up": 0,
        }

    # -- mark scaling (the one hook on the driver hot path; both scalings
    # are identity until a pressure event actually set them) --

    def scaled_marks(self, hi: int, fill: int) -> tuple[int, int]:
        if self.saturate_frac is not None:
            hi = max(1, int(hi * self.saturate_frac))
            fill = max(1, int(fill * self.saturate_frac))
        if self.fill_shrink:
            fill = max(1, fill >> self.fill_shrink)
        return hi, min(fill, hi)

    # -- signals --

    def saturate(self, frac: float) -> None:
        """Injected pool saturation (the ``saturate_pool`` fault op):
        scale the spill marks by `frac` from now on."""
        self.counters["saturations"] += 1
        self.saturate_frac = max(0.001, min(1.0, float(frac)))

    def on_pool_exhausted(self, sim, *, window=None, occupancy=None,
                          capacity=None) -> bool:
        """One pool-ladder consultation at a driver stall. True = a rung
        was taken and the driver should retry its loop; False = ladder
        exhausted (the driver drains and raises the typed error)."""
        self.counters["pool_exhausted"] += 1
        self._clean = 0
        if not self.policy.enabled:
            self.counters["gave_up"] += 1
            return False
        step = self._stall_steps
        act = sim._pressure_relieve_pool(step)
        if act is None and self.saturate_frac is not None \
                and self.saturate_frac < 1.0:
            # injected saturation yields a notch per absorbed rung —
            # the exhaust_backend recover_after contract, pool-side
            self.saturate_frac = min(1.0, self.saturate_frac * 2)
            act = "saturation_yield"
        if act is not None:
            self._stall_steps += 1
            self.counters["ladder_steps"] += 1
            self.counters[_ACTION_COUNTER[act]] += 1
            return True
        self.counters["gave_up"] += 1
        return False

    def on_backend_exhausted(self, sim, label: str = "") -> bool:
        """One memory-ladder consultation for a classified
        RESOURCE_EXHAUSTED dispatch failure (called by the supervisor).
        True = retry the dispatch; False = escalate to drain + policy."""
        self.counters["backend_exhausted"] += 1
        self._clean = 0
        if not self.policy.enabled:
            self.counters["gave_up"] += 1
            return False
        act = sim._pressure_relieve_memory(self._stall_steps)
        if act is not None:
            self._stall_steps += 1
            self.counters["ladder_steps"] += 1
            self.counters[_ACTION_COUNTER[act]] += 1
            return True
        self.counters["gave_up"] += 1
        return False

    def note_progress(self) -> None:
        """The driver observed forward progress: the current posture is
        sufficient. After `recover_after_dispatches` clean dispatches,
        relax ONE notch (shrink before gear hold — mirror of the ladder
        order) — the same hysteresis shape as GearShifter.down_after."""
        self._stall_steps = 0
        self._clean += 1
        if self._clean < self.policy.recover_after_dispatches:
            return
        self._clean = 0
        if self.fill_shrink > 0:
            self.fill_shrink -= 1
            self.counters["relaxations"] += 1
        elif self.hold_gear:
            self.hold_gear = False
            self.counters["relaxations"] += 1

    # -- telemetry --

    def stats(self) -> dict:
        """The ``pressure.*`` counters (schema v8). Integer-only — the
        float/None posture gauges ride `gauges()`."""
        d = dict(self.counters)
        d["fill_shrink"] = int(self.fill_shrink)
        d["hold_gear"] = int(self.hold_gear)
        return d

    def gauges(self) -> dict:
        return {
            "saturate_frac": (
                float(self.saturate_frac)
                if self.saturate_frac is not None else 1.0
            ),
        }


_ACTION_COUNTER = {
    "downshift": "downshifts",
    "upshift": "upshifts",
    "spill_escalation": "spill_escalations",
    "lane_eviction": "lane_evictions",
    "job_shed": "job_sheds",
    "saturation_yield": "saturation_yields",
}
