"""Device counter block: fixed-layout i64 telemetry carried in SimState.

The engine's `Counters` struct (core/state.py) accounts events and drops;
this block adds the WINDOW-plane signals every perf PR needs to watch —
which kernel path ran, how often windows shrank or rolled back, how the
per-host virtual-time frontier spreads — in a single `[NUM_WIN]` i64 array
plus two `[H]` rows, all updated inside the jitted window step with fused
adds/selects. Nothing here ever forces a host<->device sync: the block is
read only at handoff boundaries via `snapshot()` (one device_get).

Layout is versioned by position: new slots append, existing indices never
move (docs/observability.md documents the layout; BLOCK_VERSION guards
consumers).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

BLOCK_VERSION = 4

# --- fixed window-plane slot indices (append-only; never renumber) ---
WIN_WINDOWS = 0  # window steps executed (one per step() call)
WIN_MATRIX = 1  # windows dispatched on the matrix fast path
WIN_LOOP = 2  # windows dispatched on the micro-step loop path
WIN_SHRINKS = 3  # optimistic windows shrunk after a violation
WIN_ROLLBACKS = 4  # optimistic whole-window rollbacks
WIN_OPT_STALLS = 5  # optimistic null-window exchange-retry stalls
WIN_SPILL_FIRES = 6  # spill-tier manage episodes (shard rebalances)
WIN_GEAR_SHIFTS = 7  # pool gear changes (core/gearbox.py re-sorts)
WIN_FAULTS = 8  # fault-plane actions applied at handoffs (shadow_tpu/faults)
NUM_WIN = 9

WIN_NAMES = (
    "windows_run",
    "matrix_dispatches",
    "loop_dispatches",
    "window_shrinks",
    "rollbacks",
    "opt_stalls",
    "spill_fires",
    "gear_shifts",
    "fault_actions",
)
assert len(WIN_NAMES) == NUM_WIN


def win_bump_vec(*indices: int) -> jnp.ndarray:
    """Trace-time constant [NUM_WIN] vector with 1 at each index — a step
    bumps several slots with ONE fused add (win + vec)."""
    v = np.zeros((NUM_WIN,), np.int64)
    for i in indices:
        v[i] = 1
    return jnp.asarray(v)


@struct.dataclass
class ObsBlock:
    """The device-resident telemetry block (a SimState SOA field).

    Shapes: global engine win=[NUM_WIN], host rows [H]; islands layout
    win=[S, NUM_WIN] (per-shard, summed at fetch — the kernel scales
    shard-shared bumps by axis_index==0 so sums match the global engine),
    host rows [S, H/S].
    """

    win: jnp.ndarray  # [NUM_WIN] i64 window-plane counters
    host_events: jnp.ndarray  # [H] i64 committed events per host
    # Per-host virtual-time frontier: max committed event time, -1 before
    # the first commit. Never reset (unlike host.done_t): its min/max
    # spread IS the desynchronization-roughness health metric.
    host_last_t: jnp.ndarray  # [H] i64
    # Determinism-audit digest chain (obs/audit.py, block v4): rolling-mix
    # hash of every committed event key (time, src, dst, kind) in per-host
    # commit order. Rides the pytree, so rollbacks discard speculated
    # digest state with the rest of the speculated window.
    host_digest: jnp.ndarray  # [H] i64

    @classmethod
    def zeros(cls, num_hosts: int) -> "ObsBlock":
        return cls(
            win=jnp.zeros((NUM_WIN,), jnp.int64),
            host_events=jnp.zeros((num_hosts,), jnp.int64),
            host_last_t=jnp.full((num_hosts,), -1, jnp.int64),
            host_digest=jnp.zeros((num_hosts,), jnp.int64),
        )


def bump_win(state, idx: int, n: int = 1):
    """Host-side bump of one window-plane slot (driver-plane events the
    kernel cannot see: rollbacks, shrinks, spill fires). Runs at handoff
    boundaries only — a tiny device add, never a sync. No-op when the
    block is disabled or n == 0."""
    if getattr(state, "obs", None) is None or n == 0:
        return state
    w = state.obs.win
    if w.ndim == 2:  # islands layout: shard 0 carries driver-plane bumps
        w = w.at[0, idx].add(n)
    else:
        w = w.at[idx].add(n)
    return state.replace(obs=state.obs.replace(win=w))


def snapshot(state) -> dict:
    """Read the block at a handoff boundary: ONE device_get, layouts
    normalized (islands win summed over shards, host rows flattened back
    to global [H] order). Returns {} when the block is disabled."""
    if getattr(state, "obs", None) is None:
        return {}
    blk = jax.device_get(state.obs)
    win = np.asarray(blk.win)
    if win.ndim == 2:
        win = win.sum(axis=0)
    # host rows come back in GLOBAL host-id order even after an islands
    # rebalance permuted the physical layout (host.gid maps row -> host)
    gid = np.asarray(jax.device_get(state.host.gid)).reshape(-1)
    he = np.empty_like(np.asarray(blk.host_events).reshape(-1))
    he[gid] = np.asarray(blk.host_events).reshape(-1)
    hl = np.empty_like(np.asarray(blk.host_last_t).reshape(-1))
    hl[gid] = np.asarray(blk.host_last_t).reshape(-1)
    hd = np.empty_like(np.asarray(blk.host_digest).reshape(-1))
    hd[gid] = np.asarray(blk.host_digest).reshape(-1)
    return {
        "block_version": BLOCK_VERSION,
        "win": {name: int(win[i]) for i, name in enumerate(WIN_NAMES)},
        "host_events": he,
        "host_last_t": hl,
        "host_digest": hd,
    }


def committed_counters(sim) -> dict[str, int]:
    """The engine Counters at a dispatch boundary: the host copy the fused
    driver fetched with the dispatch's own outputs (`boundary_counters`,
    set only while the boundary's session reads it), else a device read
    (`sim.counters()`), as every other driver needs."""
    held = getattr(sim, "boundary_counters", None)
    return held if held is not None else sim.counters()


def vtime_stats(host_last_t: np.ndarray) -> dict:
    """Virtual-time-roughness statistics over the per-host committed-time
    frontier (cond-mat/0302050's spread metric): hosts that committed
    nothing (-1) are excluded; empty frontier reports zeros."""
    t = np.asarray(host_last_t)
    t = t[t >= 0]
    if t.size == 0:
        return {"committed_hosts": 0, "min_ns": 0, "max_ns": 0,
                "spread_ns": 0, "mean_ns": 0.0}
    return {
        "committed_hosts": int(t.size),
        "min_ns": int(t.min()),
        "max_ns": int(t.max()),
        "spread_ns": int(t.max() - t.min()),
        "mean_ns": float(t.mean()),
    }
