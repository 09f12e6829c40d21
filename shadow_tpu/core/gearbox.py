"""Occupancy-adaptive pool gearing: tiered window kernels.

The window kernel's dominant cost is its sorts and their row gathers
(_dense_extract and the merge, core/engine.py), and sort cost on TPU
scales with rows × comparator stages. Pool capacity C is a STATIC shape
compiled into the kernel, sized for the burst worst case — but PHOLD-class
steady states occupy a small fraction of it (≈ H·msgload live events), so
a pool sized 8× above occupancy wastes most of a window's wall time
sorting empty filler rows. Eiffel (arXiv:1810.03060) makes the same
observation for packet schedulers: cost must track LIVE queue occupancy,
not configured capacity; PARSIR (arXiv:2410.00644) wins by keeping
per-worker event-set working sizes small.

This module is the gearbox: a small ladder of (capacity, dense width)
tiers — e.g. C/4, C/2, C — each compiling its own window kernel, plus the
hysteresis decision rule the drivers consult at every dispatch boundary:

  * UPSHIFT immediately when occupancy (plus the headroom band) no longer
    fits under the gear's upshift mark — which sits BELOW the spill
    red-zone pressure mark, so a growing workload changes gear before the
    spill tier would have to fire;
  * DOWNSHIFT one gear only after `down_after` consecutive low-occupancy
    dispatches (oscillating workloads stay in the big gear rather than
    paying a re-sort per wave).

A gear change moves the pool between capacities with ONE truncating or
padding re-sort at the handoff boundary (resize_pool) — never inside the
jitted window loop. Semantics are exactly preserved: capacity only bounds
what fits, never the order (the pool is an unordered bag; extraction
re-sorts by the full event key every window), and the decision rule never
downshifts below live occupancy, so the truncation drops nothing. A
geared run commits the same events, counters, and final state digest as a
fixed-capacity run (tests/test_gearbox.py).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from shadow_tpu.core import simtime
from shadow_tpu.core.state import EventPool

NEVER = simtime.NEVER

# Dense-width floor: gears never shrink the per-host window below this
# (a vanishing K would defer every wave into extra window passes —
# correct, but the opposite of a perf gear).
MIN_K = 4

# Downshift hysteresis: consecutive low-occupancy dispatches required
# before dropping one gear.
DOWN_AFTER = 4


class GearSpec(NamedTuple):
    """One tier of the ladder. `capacity` is pool rows as the kernel sees
    them (per shard under islands); `K` the dense window width; `hi`/`fill`
    the spill-tier marks AT THIS CAPACITY (red-zone marks are per-gear);
    `up` the upshift threshold — occupancy at/above it wants a bigger
    gear, and it sits below `hi` so the shift happens before the spill
    red zone."""

    level: int
    capacity: int
    K: int
    hi: int
    fill: int
    up: int


def build_ladder(
    tiers: int,
    capacity: int,
    K: int,
    hosts: int,
    marks_fn: Callable[[int], tuple[int, int]],
    capacity_map: Callable[[int], int] | None = None,
) -> list[GearSpec]:
    """Build the gear ladder, ascending: tier i covers capacity >> (tiers-1-i),
    so e.g. tiers=3 gives [C/4, C/2, C]. The top gear is EXACTLY the
    configured (capacity, K) — a pool_gears=1 build is bit-identical to
    the pre-gearbox kernel. Lower gears get a matching dense width:
    K >> shift, floored so the window still covers the per-host share of a
    full pool at that gear (capacity/hosts + slack) — occupancy low enough
    to select the gear implies per-host windows that small.

    `marks_fn(capacity) -> (hi, fill)` supplies the spill marks per tier
    (spill.marks for the global engine; the islands runner passes its
    exchange-block-aware variant). Tiers whose marks are infeasible (pool
    too small for its red zone / exchange block) are skipped — except the
    top tier, whose failure propagates exactly as an ungeared build's
    would. `capacity_map` translates the global capacity of a tier into
    what the kernel actually compiles against (the islands per-shard pool
    with its structural exchange block).
    """
    if tiers < 1:
        raise ValueError("pool_gears must be >= 1")
    rows: list[tuple[int, int, int, int]] = []
    seen: set[int] = set()
    for i in range(tiers):
        shift = tiers - 1 - i
        C_g = capacity >> shift
        if capacity_map is not None:
            C_g = capacity_map(C_g)
        if C_g <= 0 or C_g in seen:
            continue
        if shift == 0:
            K_g = K
            hi, fill = marks_fn(C_g)
        else:
            K_g = min(K, max(MIN_K, K >> shift, -(-C_g // hosts) + 4))
            try:
                hi, fill = marks_fn(C_g)
            except ValueError:
                continue
        if hi <= 0:
            if shift == 0:
                raise ValueError(
                    f"pool capacity {C_g} leaves no working region above "
                    f"its red zone"
                )
            continue
        seen.add(C_g)
        rows.append((C_g, K_g, hi, fill))
    return [
        GearSpec(level=lvl, capacity=c, K=k, hi=hi, fill=fill,
                 up=(7 * hi) // 8)
        for lvl, (c, k, hi, fill) in enumerate(rows)
    ]


def target_level(ladder: list[GearSpec], occ: int, margin: int = 1) -> int:
    """Smallest gear whose upshift mark covers `occ` (× `margin` extra
    headroom — the optimistic drivers pass 2: a speculative window of
    factor F can absorb several windows' inflow between decision points).
    Falls through to the top gear when nothing smaller fits."""
    for spec in ladder:
        if occ * margin < spec.up:
            return spec.level
    return ladder[-1].level


class GearShifter:
    """The hysteresis state machine the drivers consult at dispatch
    boundaries. Pure decision logic — the Simulation owns the active
    level and performs the actual shift (pool re-sort + kernel rebind).

    Upshifts are immediate (running out of headroom risks the spill
    red zone); downshifts require `down_after` consecutive dispatches
    whose occupancy fits a smaller gear, and move ONE level at a time.
    """

    def __init__(self, ladder: list[GearSpec], down_after: int = DOWN_AFTER):
        self.ladder = ladder
        self.down_after = int(down_after)
        self._streak = 0

    def reset(self) -> None:
        self._streak = 0

    def observe(
        self, level: int, occ: int, press: bool = False, margin: int = 1
    ) -> int | None:
        """One dispatch-boundary observation; returns the level to shift
        to, or None to stay. `press` marks a red-zone early exit from the
        fused window loop — an unconditional upshift demand while a
        bigger gear exists (the gear absorbs the pressure the spill tier
        would otherwise pay host round-trips for)."""
        want = target_level(self.ladder, occ, margin)
        top = self.ladder[-1].level
        if press and level < top:
            want = max(want, level + 1)
        if want > level:
            return want
        if want < level:
            self._streak += 1
            if self._streak >= self.down_after:
                return level - 1
        else:
            self._streak = 0
        return None


class ShardGearShifter:
    """Per-shard gearing for the ASYNC islands driver (parallel/islands):
    each shard carries its own hysteresis ladder state — occupancy target,
    red-zone demand, and downshift streak — updated at its own dispatch
    boundaries from the per-shard occupancy vector the async kernel
    returns, instead of one fleet-wide state fed the pmax'd occupancy.

    The compiled tier is the ENVELOPE (max of the per-shard levels):
    under vmap every shard shares one compiled pool shape, so a single
    hot shard still sizes the batch — but a burst on one shard no longer
    resets every other shard's downshift streak, and the envelope drops
    as soon as EVERY shard's own ladder state allows it (the fleet-wide
    shifter had to watch the max-occupancy signal cross the threshold
    for `down_after` consecutive dispatches regardless of which shard
    produced each sample).
    """

    def __init__(self, ladder: list[GearSpec], num_shards: int,
                 down_after: int = DOWN_AFTER):
        self.ladder = ladder
        self.S = int(num_shards)
        self.down_after = int(down_after)
        self.levels = [ladder[-1].level] * self.S
        self._streak = [0] * self.S

    def reset(self) -> None:
        self._streak = [0] * self.S

    def seed(self, level: int) -> None:
        """Align every shard's ladder state to the bound envelope (build
        time / layout permutation / fallback checkpoint restore)."""
        self.levels = [int(level)] * self.S
        self.reset()

    def restore(self, levels, envelope: int) -> bool:
        """Re-arm the PER-SHARD ladder states a checkpoint header
        recorded (`__meta__.async.gear_levels`): a resumed mesh run keeps
        each chip's own level instead of hoisting every cool shard to the
        envelope and forgetting its downshift progress (the flat-seed
        behavior). Returns False — caller should seed() — when the
        recorded vector is absent, the wrong width, or inconsistent with
        the restored envelope (its max must equal the bound tier, or the
        compiled pool shape would disagree with the decision state).
        The width check is also the MESH-RESIZE re-seed rule: an elastic
        relayout (parallel/elastic.py) restores an S_old-chip checkpoint
        onto an S_new-chip build, whose header vector no longer describes
        this shard set — the rebuilt mesh seeds flat and re-learns its
        per-chip levels (tests/test_mesh_resilience.py exercises the
        4→3→4 round trip under a multi-tier ladder)."""
        if not levels or len(levels) != self.S:
            return False
        lv = [int(x) for x in levels]
        top = self.ladder[-1].level
        if any(x < 0 or x > top for x in lv) or max(lv) != int(envelope):
            return False
        self.levels = lv
        self.reset()
        return True

    def observe(self, level: int, occs, press=None,
                margin: int = 1) -> int | None:
        """One dispatch-boundary decision from the [S] occupancy vector
        (and optional [S] red-zone press flags). Returns the envelope
        level to shift the compiled tier to, or None to stay."""
        top = self.ladder[-1].level
        for s in range(self.S):
            want = target_level(self.ladder, int(occs[s]), margin)
            if press is not None and bool(press[s]) and self.levels[s] < top:
                want = max(want, self.levels[s] + 1)
            if want > self.levels[s]:
                self.levels[s] = want
                self._streak[s] = 0
            elif want < self.levels[s]:
                self._streak[s] += 1
                if self._streak[s] >= self.down_after:
                    self.levels[s] -= 1
                    self._streak[s] = 0
            else:
                self._streak[s] = 0
        envelope = max(self.levels)
        return envelope if envelope != level else None


def resize_pool(pool: EventPool, capacity: int):
    """Move an event pool between gear capacities at a handoff boundary.

    Growing pads free (time NEVER) rows — no sort: the pool is an
    unordered bag, slot order is immaterial (extraction re-sorts by the
    full key every window). Shrinking keeps the earliest rows by the SAME
    rule the window merge truncates with (one 1-key stable sort by time,
    free rows last), so a shrink is indistinguishable from the merge
    having run at the smaller capacity all along. Handles both the global
    [C] and the islands [S, C] layouts.

    Returns (pool, dropped) where dropped counts real rows lost to the
    truncation per leading dim — structurally zero when the caller's gear
    selection held (occupancy below the new capacity), and accounted into
    pool_overflow_dropped regardless so a decision-rule bug can never
    silently lose events.
    """
    # capacity axis is the LAST one: this runs on the host-side batched
    # layouts ([S, C] islands, [L, ..., C] fleet), where EventPool's
    # .capacity property (shape[0] — the kernel-side per-shard contract)
    # would read the batch dim instead. With that bug every islands or
    # fleet gear shift "grew" toward a capacity compared against S/L, so
    # pools inflated on every shift in either direction — bit-exact
    # (extra NEVER rows) but re-growing the sort volume the gearbox
    # exists to shrink (caught by the ISSUE-10 per-shard-gear retrace
    # test: the inflated pool shape forced a kernel re-lowering).
    C = pool.time.shape[-1]
    if capacity == C:
        return pool, jnp.zeros(pool.time.shape[:-1], jnp.int64)
    PP = pool.payload.shape[-1]
    ax = pool.time.ndim - 1  # the capacity axis (also payload's -2)
    if capacity > C:
        pad = capacity - C

        def padc(x, fill):
            cfg = [(0, 0)] * x.ndim
            cfg[ax] = (0, pad)
            return jnp.pad(x, cfg, constant_values=fill)

        grown = EventPool(
            time=padc(pool.time, NEVER),
            dst=padc(pool.dst, 0),
            src=padc(pool.src, 0),
            seq=padc(pool.seq, 0),
            kind=padc(pool.kind, 0),
            payload=padc(pool.payload, 0),
        )
        return grown, jnp.zeros(pool.time.shape[:-1], jnp.int64)
    cols = [pool.time, pool.dst, pool.src, pool.seq, pool.kind] + [
        pool.payload[..., w] for w in range(PP)
    ]
    ops = jax.lax.sort(cols, num_keys=1, is_stable=True)
    dropped = jnp.sum(
        ops[0][..., capacity:] != NEVER, axis=-1, dtype=jnp.int64
    )
    sl = (Ellipsis, slice(0, capacity))
    shrunk = EventPool(
        time=ops[0][sl], dst=ops[1][sl], src=ops[2][sl],
        seq=ops[3][sl], kind=ops[4][sl],
        payload=jnp.stack([o[sl] for o in ops[5:]], axis=-1),
    )
    return shrunk, dropped
