"""The islands runner: per-shard event pools with an all_to_all exchange.

The reference's parallel architecture is per-worker LOCALITY: hosts are
partitioned across workers (scheduler.c:329-353), each worker pops only its
own hosts' queues (scheduler_policy_host_single.c:18-54), and a cross-host
emission is one push into the owner's locked queue (scheduler.c:232-255,
worker.c:517-576). GSPMD auto-sharding of the single-pool engine reproduces
none of that locality: every shard participates in every global sort.

This module is the TPU-native equivalent of the reference design:

  * the host axis splits into S contiguous blocks ("islands"); each owns a
    LOCAL event pool (C/S rows) and a LOCAL dense window (H/S·(K+1) filler
    rows), so per-shard sort volume — the measured dominant window cost —
    drops S×;
  * cross-shard emissions ride ONE bounded all_to_all per window at the
    merge (engine._island_route): the locked-queue push becomes a
    collective;
  * the round barrier + min-next-event-time reduction (worker.c:332-363)
    becomes a lax.pmin over the shard axis;
  * rows that miss the bounded exchange defer to the next window under a
    window-end clamp (state.exch_deferred_min), so the conservative
    invariant survives backpressure — late, never lost, never reordered.

One implementation, two executions:
  mode="vmap"      S virtual islands batched on ONE chip: every local sort
                   becomes a batched sort (S× smaller rows per sort);
                   collectives lower to reshapes. This is how a single
                   TPU benefits from the islands formulation.
  mode="shard_map" S real devices on a jax Mesh: each island lives on its
                   own chip; collectives ride ICI/DCN. Same program,
                   hardware parallelism.

Determinism: per-host event order, RNG streams and sequence numbering are
functions of (seed, GLOBAL host id) only, so islands runs are bit-identical
to the global engine apart from pool-overflow timing (tests assert exact
counter equality on non-overflowing runs).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core import gearbox, simtime
from shadow_tpu.core import spill as spill_mod
from shadow_tpu.core.engine import IslandSpec, Simulation, make_window_step
from shadow_tpu.core.spill import HostSpill
from shadow_tpu.core.state import Counters, EventPool, SimState
from shadow_tpu.parallel import lookahead as lookahead_mod

AXIS = "islands"

# Per-attempt sub-step ceiling for optimistic windows: generous (a window
# of factor F needs ~F sub-steps plus exchange-retry rounds), small enough
# that a pool-headroom stall surfaces as a driver error in seconds.
_MAX_SUBSTEPS = 4096


_SIGN32 = np.int32(-(1 << 31))


def _split64(x):
    """int64 -> (high word, low word biased so signed order is unsigned
    order), both int32."""
    return (x >> 32).astype(jnp.int32), x.astype(jnp.int32) ^ _SIGN32


def _join64(hi, lo):
    return (hi.astype(jnp.int64) << 32) | (
        (lo ^ _SIGN32).astype(jnp.int64) & 0xFFFFFFFF
    )


def pmin(x, axis):
    """lax.pmin that the TPU lowers for int64 too: a 64-bit all-reduce
    lowers only as a sum there (the described v5e:2x2 compile refused the
    mesh kernel's int64 pmin, PR 21), so an int64 min is two int32
    all-reduces — the high words, then the low words of the shards that
    hold the min high word. Exact; other dtypes pass straight through."""
    if x.dtype != jnp.int64:
        return jax.lax.pmin(x, axis)
    hi, lo = _split64(x)
    m_hi = jax.lax.pmin(hi, axis)
    m_lo = jax.lax.pmin(jnp.where(hi == m_hi, lo, np.int32(2**31 - 1)),
                        axis)
    return _join64(m_hi, m_lo)


def pmax(x, axis):
    """lax.pmax counterpart of `pmin` (int64 as two int32 all-reduces)."""
    if x.dtype != jnp.int64:
        return jax.lax.pmax(x, axis)
    hi, lo = _split64(x)
    m_hi = jax.lax.pmax(hi, axis)
    m_lo = jax.lax.pmax(jnp.where(hi == m_hi, lo, _SIGN32), axis)
    return _join64(m_hi, m_lo)


# ---------------------------------------------------------------------------
# Shard kernel factories
# ---------------------------------------------------------------------------
#
# Module-level so the fleet runner (shadow_tpu/fleet) can compose them:
# vmap-of-jobs OUTSIDE, shards INSIDE (the per-shard collectives stay on
# the inner axis). `runahead` and `stop` are traced arguments — the fleet
# passes per-job values; IslandSimulation._build_gear_fns closes over its
# own runahead and delegates here.


def make_shard_run_to(step, hi: int, axis: str = AXIS):
    """Build run_to(state, params, runahead, stop, max_windows) ->
    (state, min_next, pressed, occupancy, windows) for ONE shard of the
    islands engine; wrap with vmap(axis_name=axis) over the shard axis
    (or shard_map) to get the full per-job kernel."""

    def step_shard(state, params, ws, we):
        st, mn = step(state, params, ws, we)
        return st, pmin(mn, axis)

    def _occ(state):
        return jnp.sum(state.pool.time != simtime.NEVER)

    def _press(state):
        return pmax((_occ(state) >= hi).astype(jnp.int32), axis)

    def run_to(state, params, runahead, stop, max_windows):
        runahead = jnp.asarray(runahead, jnp.int64)
        stop = jnp.asarray(stop, jnp.int64)
        max_windows = jnp.asarray(max_windows, jnp.int32)

        def cond(c):
            state, mn, w = c
            return (mn < stop) & (w < max_windows) & (_press(state) == 0)

        def body(c):
            state, mn, w = c
            ws = mn
            # exchange-backpressure clamp: never let any shard process
            # past an event still in transit (deferred exchange)
            clamp = pmin(state.exch_deferred_min, axis)
            we = jnp.minimum(jnp.minimum(ws + runahead, stop), clamp)
            state, mn = step_shard(state, params, ws, we)
            return state, mn, w + 1

        mn0 = pmin(jnp.min(state.pool.time), axis)
        state, mn, w = jax.lax.while_loop(
            cond, body, (state, mn0, jnp.int32(0))
        )
        # occupancy rides back pmax'd: the gearing decision covers the
        # FULLEST shard (every shard's pool compiles the same capacity)
        occ = pmax(_occ(state), axis)
        return state, mn, _press(state) > 0, occ, w

    return run_to


def make_shard_run_to_async(step, hi: int, axis: str = AXIS,
                            shifts: tuple[int, ...] | None = None,
                            num_shards: int | None = None):
    """Build run_to(state, params, runahead, look_in, spread, stop,
    max_windows) -> (state, min_next, pressed, occupancy, windows,
    frontier, spread_max, steps, yields, blocked) — the ASYNCHRONOUS
    conservative window loop (cs/0409032) for ONE shard of the islands
    engine; wrap with vmap(axis_name=axis) over the shard axis (or
    shard_map) to get the full kernel.

    With `shifts` (a static tuple of ring shifts covering every finite
    in-edge — parallel/lookahead.ppermute_shifts — plus `num_shards`),
    the frontier/minimum exchange is NEIGHBOR-ONLY: one
    ``jax.lax.ppermute`` per shift instead of an ``all_gather`` over
    the shard axis, so per-chip collective volume under shard_map is
    len(shifts) scalars per superstep (topology degree), not S (mesh
    size), and the optimized HLO of the mesh kernel carries ZERO
    all-gather ops (hlo_audit-gated). shifts=None keeps the all_gather
    exchange — the bench comparison arm. Both arms compute the
    identical horizon, so committed events and audit chains are
    bit-identical.

    Where make_shard_run_to's barrier loop advances every shard to one
    fleet-wide frontier per window (ws = pmin of all local minima), each
    shard here carries its OWN virtual-time frontier in the loop carry
    and steps its own window [mn_local, mn_local + runahead_local)
    whenever its next local event lies below its safe horizon

        horizon_i = min over in-neighbors j of  frontier[j] + look_in[j]

    with the lookahead matrix derived from the baked topology at
    partition time (parallel/lookahead.py). Shards with no admissible
    work run a NULL window at their frontier — under vmap every shard
    rides the batched step anyway, so the bounded all_to_all exchange
    retries deferred rows every superstep — and advance their frontier
    to the horizon (the async protocol's null-message advance). When NO
    shard can step, every frontier jumps to the global next-event time
    (all future events derive from events at or after it): the barrier
    driver's ws = global-min gap jump, recovered for idle regions.

    Roughness suppression (cond-mat/0302050): a shard more than `spread`
    ns above the minimum frontier yields its slot (a null window),
    keeping the virtual-time surface flat so run-ahead pool/exchange
    buffering stays bounded; the minimum-frontier shard can never yield,
    so progress is unconditional. `runahead` (per shard), `look_in`
    ([S] in-edge lookahead, NEVER = unconstrained), `spread` and `stop`
    are all TRACED — the fleet passes per-lane values, and a rebalance
    re-derives the matrix without recompiling.

    The conservative invariant, per superstep: shard j's emissions in
    [ws_j, we_j) land at or after ws_j + L[j->i] >= frontier[j] +
    look_in[j->i] >= horizon_i >= we_i, so nothing i processes this
    superstep can be overtaken by an in-flight delivery. That LBTS
    argument covers only events still to be EMITTED; a deferred
    exchange row has already been emitted and paid its path latency —
    it lands at its pool time, NOT at source-frontier + L — so both
    the running horizon and the initial frontier f0 must additionally
    min against the gathered exch_deferred_min (the earliest
    in-transit row fleet-wide). Committed per-host event order is
    identical to the barrier schedule, so the audit digest chain is
    bit-identical (tests/test_async_sync.py).
    """

    NEV = jnp.int64(simtime.NEVER)

    if shifts is not None:
        if num_shards is None:
            raise ValueError(
                "make_shard_run_to_async(shifts=...) needs num_shards "
                "(the ppermute schedule is a static compiled property)"
            )
        S = int(num_shards)
        _perms = [
            [(j, (j + int(d)) % S) for j in range(S)] for d in shifts
        ]

    def _occ(state):
        return jnp.sum(state.pool.time != simtime.NEVER)

    def _press(state):
        return pmax((_occ(state) >= hi).astype(jnp.int32), axis)

    def run_to(state, params, runahead, look_in, spread, stop, max_windows):
        runahead = jnp.asarray(runahead, jnp.int64)
        look_in = jnp.asarray(look_in, jnp.int64)
        spread = jnp.asarray(spread, jnp.int64)
        stop = jnp.asarray(stop, jnp.int64)
        max_windows = jnp.asarray(max_windows, jnp.int32)

        # min over in-neighbors j of vec[j] + L[j->i], guarded against
        # i64 overflow (NEVER is the i64 max): an unreachable edge, or a
        # neighbor already at stop (it will never emit below stop + L),
        # is unconstraining. Two exchanges, one horizon: the all_gather
        # arm ships every shard's value; the ppermute arm ships only the
        # covered in-edges (one collective-permute per static shift, the
        # neighbor's lookahead read from the traced look_in row at
        # (i - shift) mod S) — identical value, degree-scaled volume.
        if shifts is None:
            def _bound(vec):
                allv = jax.lax.all_gather(vec, axis)  # [S]
                nocon = (look_in >= NEV) | (allv >= stop)
                return jnp.min(jnp.where(nocon, NEV, allv + look_in))
        else:
            def _bound(vec):
                i = jax.lax.axis_index(axis)
                iota = jnp.arange(S, dtype=jnp.int32)
                acc = NEV
                for d, perm in zip(shifts, _perms):
                    recv = jax.lax.ppermute(vec, axis, perm)
                    # the delivering neighbor's in-edge lookahead, read
                    # from the traced row by masked reduce (no gather —
                    # the rank the shard vmap adds would otherwise turn
                    # an index into a per-element fetch the HLO audit
                    # bans): non-selected entries are NEVER, so the min
                    # IS the selected entry
                    j = jnp.mod(i - int(d), S).astype(jnp.int32)
                    w = jnp.min(jnp.where(iota == j, look_in, NEV))
                    nocon = (w >= NEV) | (recv >= stop)
                    acc = jnp.minimum(
                        acc, jnp.where(nocon, NEV, recv + w)
                    )
                return acc

        def _horizon(frontier, state):
            bound = _bound(frontier)
            defer = pmin(state.exch_deferred_min, axis)
            return jnp.minimum(jnp.minimum(bound, defer), stop)

        def cond(c):
            state, frontier, mn, w, _ = c
            live = pmin(frontier, axis) < stop
            return live & (w < max_windows) & (_press(state) == 0)

        def body(c):
            state, frontier, mn, w, stats = c
            spread_max, steps, yields, blocked = stats
            hz = _horizon(frontier, state)
            minF = pmin(frontier, axis)
            maxF = pmax(frontier, axis)
            spread_max = jnp.maximum(spread_max, maxF - minF)
            mn_all = pmin(mn, axis)
            has_work = (mn < hz) & (mn < stop)
            # roughness suppression (cond-mat/0302050): a shard whose
            # frontier — or whose NEXT window — sits more than `spread`
            # above the minimum frontier yields its slot; the minimum-
            # frontier shard can never lag, so progress is unconditional
            cap = minF + spread
            lag = (frontier > cap) | (mn > cap)
            stepped = has_work & ~lag
            ws = jnp.where(stepped, mn, frontier)
            we = jnp.where(
                stepped,
                jnp.minimum(jnp.minimum(ws + runahead, hz), stop),
                ws,
            )
            state, mn2 = step(state, params, ws, jnp.maximum(we, ws))
            # frontier advance — for every non-yielding shard, as far as
            # all three bounds allow: min(local min after the step,
            # horizon, roughness cap). A stepped shard that cleared its
            # pool leaps straight past the window end toward its next
            # event (the null-message advance fused into the same
            # superstep); rank-deferred in-window leftovers hold it at
            # mn2 < we; an idle shard advances to its horizon; a
            # yielding shard holds. Exchange arrivals of THIS superstep
            # land at or after the pre-step horizon, so min(mn2, hz)
            # never overtakes one.
            raw = jnp.where(
                has_work & lag, frontier, jnp.minimum(mn2, hz)
            )
            adv = jnp.minimum(raw, jnp.maximum(frontier, cap))
            clipped = raw > adv  # null-advance suppressed by the cap
            any_step = pmax(stepped.astype(jnp.int32), axis) > 0
            # gap jump, exempt from the cap: it raises the MINIMUM
            # frontier too, so the surface moves up flat
            adv = jnp.where(
                any_step, adv,
                jnp.maximum(adv, jnp.minimum(mn_all, stop)),
            )
            frontier = jnp.maximum(frontier, jnp.minimum(adv, stop))
            one = jnp.int64(1)
            zero = jnp.int64(0)
            stats = (
                spread_max,
                steps + jnp.where(stepped, one, zero),
                yields + jnp.where((has_work & lag) | clipped, one, zero),
                blocked + jnp.where((mn < stop) & (mn >= hz), one, zero),
            )
            return state, frontier, mn2, w + 1, stats

        mn0 = jnp.min(state.pool.time)
        # per-dispatch frontier re-derivation from pool state alone, so
        # the restart is safe after any host-side interruption (spill
        # manage, fault drain, checkpoint resume, gear resize). Two
        # bounds, both required: events still TO BE EMITTED by shard j
        # cannot arrive at i below mn_j + L[j->i]; events ALREADY
        # emitted but in transit (deferred exchange rows) have paid
        # their path latency and land at their pool time — they are
        # bounded only by the gathered exch_deferred_min, exactly as in
        # _horizon. Omitting the deferred clamp would charge an
        # in-transit row its link latency a second time and initialize
        # the destination frontier past the row's landing time — a
        # silent causality violation once the row lands. (_bound treats
        # a neighbor minimum at/above stop as unconstraining; that term
        # could only have exceeded stop anyway, and f0 mins with stop.)
        f0 = jnp.minimum(
            jnp.minimum(
                jnp.minimum(mn0, _bound(mn0)),
                pmin(state.exch_deferred_min, axis),
            ),
            stop,
        )
        z = jnp.int64(0)
        state, frontier, mn, w, stats = jax.lax.while_loop(
            cond, body, (state, f0, mn0, jnp.int32(0), (z, z, z, z))
        )
        spread_max, steps, yields, blocked = stats
        return (
            state, pmin(mn, axis), _press(state) > 0, _occ(state),
            w, frontier, spread_max, steps, yields, blocked,
        )

    return run_to


def make_shard_substep(step, axis: str = AXIS):
    """Build substep(state, params, ws, we) -> (state, min_next, viol)
    for ONE shard of the optimistic islands engine: one window sub-step
    with the frontier and earliest-violation scalars pmin-combined so
    every shard reports the same values."""

    def substep(state, params, ws, we):
        st2, mn2 = step(state, params, ws, we)
        mn2 = pmin(mn2, axis)
        viol = pmin(st2.xmit_min, axis)
        return st2, mn2, viol

    return substep


# ---------------------------------------------------------------------------
# State layout transform: global [H]/[C] arrays → per-shard [S, ...] blocks
# ---------------------------------------------------------------------------


def _split_host_leaf(x, S: int, H: int):
    """[H, ...] → [S, H/S, ...]; scalars → shard-0-holds-value (summed at
    fetch, so counter aggregation stays exact)."""
    x = jnp.asarray(x)
    if x.ndim >= 1 and x.shape[0] == H:
        return x.reshape((S, H // S) + x.shape[1:])
    if x.ndim == 0:
        z = jnp.zeros((S,), x.dtype)
        return z.at[0].set(x)
    raise ValueError(
        f"sub-state leaf with shape {x.shape} is neither [H]-leading nor "
        f"scalar; the islands layout cannot place it"
    )


def islandize_state(state: SimState, S: int, C_shard: int) -> SimState:
    """Rebuild a freshly-built GLOBAL SimState in the [S, ...] islands
    layout: host rows block-partitioned, pool rows routed to their
    destination's shard, counters/scalars summed-at-fetch."""
    H = state.host.gid.shape[0]
    if H % S:
        raise ValueError(f"num_hosts {H} must divide by num_shards {S}")
    Hl = H // S

    # --- pool: route rows home by dst block (np on host; build-time) ---
    pool = jax.device_get(state.pool)
    C = state.pool.capacity
    PPcols = pool.payload.shape[1]
    live = pool.time != simtime.NEVER
    t = np.full((S, C_shard), simtime.NEVER, np.int64)
    d = np.zeros((S, C_shard), np.int32)
    s_ = np.zeros((S, C_shard), np.int32)
    q = np.zeros((S, C_shard), np.int32)
    k = np.zeros((S, C_shard), np.int32)
    p = np.zeros((S, C_shard, PPcols), np.int64)
    for sh in range(S):
        rows = np.where(live & (pool.dst // Hl == sh))[0]
        if len(rows) > C_shard:
            raise ValueError(
                f"shard {sh} initial events ({len(rows)}) exceed per-shard "
                f"pool capacity {C_shard}"
            )
        n = len(rows)
        t[sh, :n] = pool.time[rows]
        d[sh, :n] = pool.dst[rows]
        s_[sh, :n] = pool.src[rows]
        q[sh, :n] = pool.seq[rows]
        k[sh, :n] = pool.kind[rows]
        p[sh, :n] = pool.payload[rows]
    new_pool = EventPool(
        time=jnp.asarray(t), dst=jnp.asarray(d), src=jnp.asarray(s_),
        seq=jnp.asarray(q), kind=jnp.asarray(k), payload=jnp.asarray(p),
    )

    host = jax.tree.map(lambda x: _split_host_leaf(x, S, H), state.host)
    subs = jax.tree.map(lambda x: _split_host_leaf(x, S, H), state.subs)
    counters = jax.tree.map(lambda x: _split_host_leaf(x, S, H),
                            state.counters)
    obs = state.obs
    if obs is not None:
        # telemetry block: host rows block-partition like everything else;
        # the window-plane row is per-shard (the kernel scales shared
        # bumps by axis_index==0, so the fetch-time sum matches the
        # global engine's counts)
        obs = obs.replace(
            win=jnp.zeros((S,) + obs.win.shape, obs.win.dtype)
            .at[0].set(obs.win),
            host_events=obs.host_events.reshape((S, Hl)),
            host_last_t=obs.host_last_t.reshape((S, Hl)),
            host_digest=obs.host_digest.reshape((S, Hl)),
        )
    flight = state.flight
    if flight is not None:
        # flight ring rows are host-indexed: block-partition like every
        # other host leaf ([H, R] -> [S, Hl, R], count [H] -> [S, Hl])
        flight = jax.tree.map(
            lambda x: _split_host_leaf(x, S, H), flight
        )
    bcast = lambda v: jnp.broadcast_to(jnp.asarray(v), (S,))  # noqa: E731
    return state.replace(
        pool=new_pool,
        host=host,
        subs=subs,
        counters=counters,
        obs=obs,
        flight=flight,
        rng_keys=state.rng_keys.reshape((S, Hl) + state.rng_keys.shape[1:]),
        now=bcast(state.now),
        xmit_min=bcast(state.xmit_min),
        exch_deferred_min=bcast(state.exch_deferred_min),
    )


def deislandize_host_array(x, *trailing):
    """[S, H/S, ...] → [H, ...] (for tracker/observability fetch)."""
    x = np.asarray(x)
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def globalize_state(foreign: SimState, pool_capacity: int) -> SimState:
    """Invert the islands layout: a [S, ...] (possibly migrated) SimState
    back to the CANONICAL global layout — host rows in global-id order
    (state.host.gid is the authority; a checkpoint taken after a live
    migration carries permuted rows), live pool rows compacted into a
    [pool_capacity] pool in full-event-key order, per-shard counter rows
    summed, clocks reduced (now = max frontier, xmit_min = min), and the
    exchange-deferral clamp cleared (every re-routed row is home — no row
    is in transit in a single global pool).

    This is the checkpoint→resume re-layout seam (core/checkpoint.
    restore_relayout): a mesh checkpoint resumes on a DIFFERENT mesh size
    (or on the global engine) by globalizing here and re-islandizing for
    the target partition. Pure host-side numpy; determinism is free —
    per-host order, RNG streams and digests key on global host ids, so
    the audit chain is preserved exactly."""
    gid = np.asarray(jax.device_get(foreign.host.gid))
    batched = gid.ndim == 2
    S_old = gid.shape[0] if batched else 1
    H = int(gid.reshape(-1).shape[0])
    flat_gid = gid.reshape(-1)
    inv = np.empty(H, np.int64)
    inv[flat_gid] = np.arange(H, dtype=np.int64)

    def canon(x):
        x = np.asarray(jax.device_get(x))
        flat = x.reshape((H,) + x.shape[2:]) if batched else x
        return jnp.asarray(flat[inv])

    def host_like(x):
        """Host-indexed leaf ([S, Hl, ...] or [H, ...]) → canonical
        [H, ...]; per-shard scalar rows ([S]) → summed scalar."""
        x = np.asarray(jax.device_get(x))
        if batched and x.ndim >= 2 and x.shape[:2] == (S_old, H // S_old):
            return jnp.asarray(x.reshape((H,) + x.shape[2:])[inv])
        if batched and x.shape == (S_old,):
            return jnp.asarray(x.sum())
        return jnp.asarray(x)

    # --- pool: compact live rows in full-event-key order ---
    pt = np.asarray(jax.device_get(foreign.pool.time)).reshape(-1)
    cols = [
        np.asarray(jax.device_get(c)).reshape((-1,) + c.shape[2:] if batched
                                              else c.shape)
        for c in (foreign.pool.dst, foreign.pool.src, foreign.pool.seq,
                  foreign.pool.kind, foreign.pool.payload)
    ]
    live = np.flatnonzero(pt != simtime.NEVER)
    if live.shape[0] > pool_capacity:
        raise ValueError(
            f"{live.shape[0]} live pool rows exceed the target pool "
            f"capacity {pool_capacity}; raise experimental.event_capacity "
            f"on the resuming build"
        )
    order = live[np.lexsort((
        cols[2][live], cols[1][live], cols[0][live], pt[live]
    ))]
    C = int(pool_capacity)
    t = np.full((C,), simtime.NEVER, np.int64)
    n = order.shape[0]
    t[:n] = pt[order]
    out_cols = []
    for c in cols:
        buf = np.zeros((C,) + c.shape[1:], c.dtype)
        buf[:n] = c[order]
        out_cols.append(buf)
    pool = EventPool(
        time=jnp.asarray(t), dst=jnp.asarray(out_cols[0]),
        src=jnp.asarray(out_cols[1]), seq=jnp.asarray(out_cols[2]),
        kind=jnp.asarray(out_cols[3]), payload=jnp.asarray(out_cols[4]),
    )

    obs = foreign.obs
    if obs is not None:
        obs = obs.replace(
            # the window-plane row: per-shard bumps sum to the global
            # engine's counts (islandize's inverse)
            win=jnp.asarray(np.asarray(
                jax.device_get(obs.win)
            ).sum(axis=0) if batched else jax.device_get(obs.win)),
            host_events=canon(obs.host_events),
            host_last_t=canon(obs.host_last_t),
            host_digest=canon(obs.host_digest),
        )
    red = lambda x, f: jnp.asarray(  # noqa: E731
        f(np.asarray(jax.device_get(x))))
    return foreign.replace(
        pool=pool,
        host=jax.tree.map(canon, foreign.host),
        subs=jax.tree.map(host_like, foreign.subs),
        counters=jax.tree.map(host_like, foreign.counters),
        obs=obs,
        flight=(
            jax.tree.map(canon, foreign.flight)
            if foreign.flight is not None else None
        ),
        rng_keys=canon(foreign.rng_keys),
        now=red(foreign.now, np.max),
        xmit_min=red(foreign.xmit_min, np.min),
        exch_deferred_min=jnp.asarray(np.int64(simtime.NEVER)),
    )


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


class IslandSimulation(Simulation):
    """Simulation whose window kernel runs as S islands.

    Accepts every Simulation kwarg plus:
      num_shards      S (must divide num_hosts)
      exchange_slots  X rows per destination shard per window (0 = auto:
                      sized from EXPECTED per-window cross-shard traffic,
                      C/(2·S²) with a floor of 64; misses defer safely,
                      so undersizing costs window clamps, while
                      oversizing re-grows sort volume — see __init__)
      mode            "vmap" (virtual islands, one device) or "shard_map"
                      (one island per mesh device)
      exchange        async frontier-exchange collective: "ppermute"
                      (neighbor-only, one collective-permute per static
                      ring shift covering the in-edge matrix — per-chip
                      volume scales with topology degree) or "all_gather"
                      (every shard's frontier every superstep — the
                      bench comparison arm). Identical horizons, chains
                      bit-identical.
      placement       initial host→chip assignment: "block" (contiguous
                      global-id blocks) or "min_cut" (greedy affinity
                      clustering, parallel/balancer.min_cut_placement —
                      lookahead-critical links land intra-chip; implies
                      the rebalance-capable slot_of kernel)
      force_path      optional engine path pin. Under vmap a lax.cond with
                      a batched predicate executes BOTH branches, so
                      matrix-capable sims (PHOLD) should pin "matrix" —
                      sound whenever the bulk contract is static.
    """

    def __init__(self, *, num_shards: int, exchange_slots: int = 0,
                 mode: str = "vmap", force_path: str | None = None,
                 rebalance: bool = False, pool_gears: int = 1,
                 async_sync: bool = True, async_spread: int = 0,
                 balancer: bool = False, balancer_policy=None,
                 exchange: str = "ppermute", placement: str = "block",
                 exclude_chips: tuple = (), **kw):
        if mode not in ("vmap", "shard_map"):
            raise ValueError(f"unknown islands mode {mode!r}")
        if exchange not in ("ppermute", "all_gather"):
            raise ValueError(f"unknown islands exchange {exchange!r}")
        if placement not in ("block", "min_cut"):
            raise ValueError(f"unknown islands placement {placement!r}")
        self.num_shards = int(num_shards)
        self.mode = mode
        self._exchange = exchange
        self.placement = placement
        self.exclude_chips = tuple(int(c) for c in exclude_chips)
        if placement == "min_cut":
            # the placement permutes host→slot at build time through the
            # same seam a live rebalance uses, so it needs the slot_of
            # routing table compiled in
            rebalance = True
        # the balancer migrates through the slot_of routing seam, so
        # enabling it implies the rebalance-capable kernel
        self.rebalance_enabled = bool(rebalance) or bool(balancer)
        self.rebalances = 0
        # Asynchronous conservative sync (cs/0409032): the fused
        # conservative driver runs per-shard virtual-time frontiers with
        # topology-derived lookahead instead of the lockstep window
        # barrier. experimental.async_islands: false restores the
        # barrier loop (the bench comparison arm).
        self._async = bool(async_sync)
        if int(async_spread) < 0:
            raise ValueError("async_spread must be >= 0 ns (0 = auto)")
        self._async_spread_cfg = int(async_spread)
        H = kw["num_hosts"]
        S = self.num_shards
        if H % S:
            raise ValueError(f"num_hosts {H} must divide by num_shards {S}")
        Hl = H // S
        C = kw.get("event_capacity", 1 << 14)
        if exchange_slots <= 0:
            # Typical-case sizing from EXPECTED cross-shard traffic, not
            # the worst case. Per window a shard commits at most its live
            # rows (≤ C/S, and capacity is user-sized to ~1.5× the live
            # population); uniform destinations put 1/S of emissions on
            # each of the S−1 foreign shards, so expected rows per
            # (src, dst, window) ≈ C/(1.5·S²). Misses defer safely under
            # the exch_deferred_min window-end clamp (late, never lost),
            # so X is a PERF knob — and an oversized X is itself a perf
            # bug: the exchange block occupies S·X pool rows structurally
            # and rides every grouping sort as S·X filler rows, so
            # inflating it re-grows the very sort volume the islands
            # formulation exists to shrink. (Round 4 shipped a worst-case
            # formula, Hl·O/S, that made each shard's pool LARGER than the
            # global pool at the 8-device dryrun shape — VERDICT r4 weak
            # #1. Measured traffic there was ~112 rows/pair/window; this
            # formula gives 192 at that shape.) Tune from a live run with
            # suggest_exchange_slots().
            exchange_slots = max(64, C // (2 * S * S))
        self.exchange_slots = int(exchange_slots)
        # The exchange block occupies S·X pool slots STRUCTURALLY (the
        # received rows land in the pool's tail block each window, mostly
        # fillers), so the per-shard pool is the per-shard share of the
        # configured capacity PLUS that block — otherwise the block eats
        # real event storage and the shard overflows at C/S − S·X.
        C_shard = (C + S - 1) // S + S * self.exchange_slots
        if S > 1 and C_shard >= C:
            raise ValueError(
                f"islands sizing defeats itself: per-shard pool "
                f"{C_shard} (= capacity/{S} + {S}x{self.exchange_slots} "
                f"exchange block) is not smaller than the global pool "
                f"{C}, so per-shard sort volume would exceed the "
                f"single-pool engine's — the S× locality win inverts. "
                f"Lower exchange_slots (misses defer safely) or raise "
                f"event_capacity."
            )
        kw = dict(kw)
        kw["pool_gears"] = 1  # global build first (islandized below); the
        # islands ladder replaces the global one with per-shard capacities
        super().__init__(**kw)

        # Topology-derived async-sync bounds (parallel/lookahead.py):
        # per-shard-pair lookahead matrix + per-shard safe window widths,
        # re-derived (never recompiled — the kernel takes them as traced
        # arguments) whenever the host->shard assignment changes
        # (rebalance_now / resume of a rebalanced layout).
        self._latency_np = np.asarray(
            jax.device_get(self.params.latency_vv))
        self._host_vertex_g = np.asarray(kw["host_vertex"], dtype=np.int64)
        self._lookahead = lookahead_mod.derive(
            self._latency_np, self._host_vertex_g, S
        )
        self._refresh_async_args()
        # the compiled neighbor-exchange schedule: ring ppermute shifts
        # covering every finite in-edge of the partition (a static
        # kernel property — lookahead VALUES stay traced). Re-derived
        # below if a min-cut placement changes shard connectivity;
        # _ensure_shift_coverage widens it (one counted rebuild) if a
        # later rebalance ever introduces an uncovered edge.
        self._async_shifts = lookahead_mod.ppermute_shifts(self._lookahead)
        self._exchange_rebuilds = 0
        self._mesh_collective_bytes = 0
        self._async_counters = {
            "dispatches": 0, "supersteps": 0, "shard_windows": 0,
            "yields": 0, "blocked_on_neighbor": 0,
        }
        self._async_spread_max = 0
        self._async_frontier = None
        # cumulative per-shard [3, S] (steps / yields / blocked) — the
        # critical-path attribution signal (obs/prof.py); reset when an
        # elastic relayout changes S
        self._async_shard_stats = np.zeros((3, S), np.int64)
        self._look_in_cache = None

        spec = IslandSpec(
            axis=AXIS, num_shards=S, exchange_slots=self.exchange_slots,
            use_slot_table=self.rebalance_enabled,
        )
        self._island_spec = spec
        self._force_path = force_path

        # Islands gear ladder (core/gearbox.py): tiers over the GLOBAL
        # capacity, each mapped to its per-shard pool (share + structural
        # exchange block) with exchange-aware red-zone marks. Tiers whose
        # per-shard pool can't hold the exchange block + red zone are
        # skipped; the top tier is exactly the pre-gearbox C_shard.
        SX = S * self.exchange_slots

        def island_marks(C_s: int) -> tuple[int, int]:
            """Per-gear marks: the merge truncates the remainder at
            C_keep = C_shard − S·X (the exchange block structurally
            occupies the pool tail), so pressure must fire below C_keep,
            not raw capacity."""
            keep = C_s - SX
            hi = keep - spill_mod.red_zone(C_s)
            if hi <= 0:
                raise ValueError(
                    "per-shard pool too small for its exchange block + "
                    "red zone; raise event_capacity or lower "
                    "exchange_slots"
                )
            return hi, max(1, (3 * hi) // 4)

        self.pool_gears = int(pool_gears)
        self._gear_ladder = gearbox.build_ladder(
            self.pool_gears, C, self.K, Hl, island_marks,
            capacity_map=lambda c: (c + S - 1) // S + SX,
        )
        # initial gear from the per-shard initial occupancy (max shard)
        pt = np.asarray(jax.device_get(self.state.pool.time))
        pd = np.asarray(jax.device_get(self.state.pool.dst))
        live = pt != simtime.NEVER
        occ0 = int(np.bincount(
            pd[live] // Hl, minlength=S
        ).max()) if live.any() else 0
        self._gear = (
            gearbox.target_level(self._gear_ladder, occ0)
            if len(self._gear_ladder) > 1
            else self._gear_ladder[-1].level
        )
        self._shifter = (
            gearbox.GearShifter(self._gear_ladder)
            if len(self._gear_ladder) > 1
            else None
        )
        # Per-shard gears for the async driver (gearbox.ShardGearShifter):
        # each shard's ladder state advances at its own dispatch
        # boundaries from the per-shard occupancy vector; the compiled
        # tier is the envelope (vmap shares one pool shape). The scalar
        # shifter stays bound for the barrier/stepwise/optimistic paths.
        self._shard_shifter = (
            gearbox.ShardGearShifter(self._gear_ladder, S)
            if self._async and len(self._gear_ladder) > 1
            else None
        )
        if self._shard_shifter is not None:
            self._shard_shifter.seed(self._gear)
        self._gear_shifts = 0
        self._gear_dispatches = {}
        self._C_shard = self._gear_ladder[self._gear].capacity
        # Re-layout the built global state into islands.
        self.state = islandize_state(self.state, S, self._C_shard)
        if self.rebalance_enabled:
            # identity assignment to start; the table is a runtime param,
            # so later rebalances never recompile
            self.params = self.params.replace(
                slot_of=jnp.arange(H, dtype=jnp.int32)
            )

        def build_step(sp: IslandSpec, K: int):
            return make_window_step(
                self.handlers, Hl, K=K, B=self.B, O=self.O,
                bulk_kinds=self._bulk_kinds,
                matrix_handlers=self._matrix_handlers,
                with_cpu_model=self._with_cpu,
                bulk_gate=self._bulk_gate,
                bulk_self_excluded=self._bulk_self_excluded,
                payload_words=self._payload_words,
                island=sp,
                audit=self._audit_digest,
                _force_path=force_path,
            )

        self._step_builder = build_step

        self.mesh = None
        if mode == "vmap":
            # self._jit honors supervisor CPU failover (core/supervisor):
            # kernels re-lower on the CPU backend while the accelerator
            # is gone. `rest_shard` marks which trailing kernel arguments
            # (after state, params) carry per-shard data — the async
            # loop's [S] runahead vector and [S, S] lookahead matrix.
            def _wrap(fn, n=1, rest_shard=(False, False)):
                in_axes = (0, None) + tuple(
                    0 if sh else None for sh in rest_shard
                )
                return self._jit(jax.vmap(
                    fn, in_axes=in_axes, axis_name=AXIS
                ))

            self._wrap = _wrap
        else:  # shard_map: _wrap is defined below with the mesh in scope
            from jax.sharding import PartitionSpec as P

            from shadow_tpu.parallel import mesh as mesh_mod

            # deterministically-ordered device mesh (parallel/mesh.py:
            # one axis, S chips) — the same construction every process
            # of a multi-host run resolves to. `exclude_chips` names
            # dead devices the surviving-mesh rebuild must skip
            # (elastic resilience, parallel/elastic.py).
            mesh = mesh_mod.host_mesh(
                S, axis=AXIS, exclude=tuple(exclude_chips)
            )
            self.mesh = mesh

            def _sq(tree):
                return jax.tree.map(lambda x: x[0], tree)

            def _unsq(tree):
                return jax.tree.map(lambda x: x[None], tree)

            state_spec = jax.tree.map(
                lambda _: P(AXIS), self.state,
            )
            params_spec = jax.tree.map(lambda _: P(), self.params)

            def sm(fn, n_scalar_out, rest_shard=(False, False)):
                def body(state, params, *rest):
                    vals = [
                        jax.tree.map(lambda x: x[0], r) if sh else r
                        for r, sh in zip(rest, rest_shard)
                    ]
                    out = fn(_sq(state), params, *vals)
                    return (_unsq(out[0]),) + tuple(
                        o[None] for o in out[1:]
                    )

                wrapped = jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(state_spec, params_spec) + tuple(
                        P(AXIS) if sh else P() for sh in rest_shard
                    ),
                    out_specs=(state_spec,) + (P(AXIS),) * n_scalar_out,
                    # the fused while_loops carry pmin-reduced scalars back
                    # into varying state fields (e.g. state.now ← window
                    # start): semantically sound — every shard computes the
                    # identical value from the collective — but the static
                    # varying-manual-axes checker can't see that, so it is
                    # disabled for these wrappers
                    check_vma=False,
                )
                return self._jit(wrapped)

            self._wrap = sm
        # min-cut host->chip placement (parallel/balancer.py): cluster
        # high-affinity (low-latency) hosts onto one chip at partition
        # time, through the same slot_of permutation seam a live
        # rebalance uses — applied BEFORE the kernels bind so the
        # ppermute schedule compiles against the placed connectivity
        if placement == "min_cut" and S > 1:
            from shadow_tpu.parallel import balancer as balancer_mod

            slot = balancer_mod.min_cut_placement(
                self._latency_np, self._host_vertex_g, S
            )
            if not np.array_equal(
                slot, np.arange(H, dtype=slot.dtype)
            ):
                self.migrate_hosts(slot)
                self.rebalances = 0  # a build-time placement, not a heal
            # the schedule compiles against the PLACED connectivity (the
            # kernels bind below), so re-narrow past the transitional
            # union _ensure_shift_coverage took and zero its counter —
            # nothing was rebuilt, nothing had compiled yet
            self._async_shifts = lookahead_mod.ppermute_shifts(
                self._lookahead
            )
            self._exchange_rebuilds = 0
        # shard_map: pin every [S, ...] state leaf to its chip so the
        # first dispatch starts resident instead of paying a reshard
        self._place_state()
        # drop the GLOBAL-layout kernels super().__init__ bound and rebind
        # the islands kernels for the active gear (one compiled set per
        # gear level, cached in _gear_fns like the global engine's)
        self._gear_fns = {}
        self._bind_gear()
        self.windows_run = 0  # dispatched windows (suggest_exchange_slots)
        # Self-balancing plane (parallel/balancer.py): the closed-loop
        # hot-shard controller, consulted at every fused-dispatch
        # boundary by run(). None = detection-only telemetry (the async
        # posture still rides metrics; nothing acts on it).
        self.balancer = None
        if balancer:
            from shadow_tpu.parallel import balancer as balancer_mod

            self.balancer = balancer_mod.ShardBalancer(balancer_policy)

    def _build_gear_fns(self, spec: gearbox.GearSpec) -> dict:
        if getattr(self, "_step_builder", None) is None:
            # super().__init__ pre-build (global layout, discarded once the
            # islands ladder rebinds below)
            return super()._build_gear_fns(spec)
        step = self._step_builder(self._island_spec, spec.K)
        runahead = jnp.int64(self.runahead)
        lane_run_to = make_shard_run_to(step, spec.hi)

        def step_shard(state, params, ws, we):
            st, mn = step(state, params, ws, we)
            return st, pmin(mn, AXIS)

        def run_to(state, params, stop, max_windows):
            return lane_run_to(state, params, runahead, stop, max_windows)

        fns = {
            "step_fn": step,
            "step": self._wrap(step_shard, 1),
            "run_to": self._wrap(run_to, 4),
            # the optimistic sub-step kernel compiles lazily per gear
            # (_ensure_optimistic): conservative runs never pay for it
            "attempt": None,
        }
        if self._async:
            # the async conservative loop: per-shard [S] runahead and
            # [S, S] in-edge lookahead ride as per-shard traced inputs;
            # the neighbor-only ppermute schedule (when configured) is a
            # static closure over the covering ring shifts
            shifts = (
                self._async_shifts if self._exchange == "ppermute"
                else None
            )
            fns["run_to_async"] = self._wrap(
                make_shard_run_to_async(
                    step, spec.hi, shifts=shifts,
                    num_shards=self.num_shards,
                ), 9,
                rest_shard=(True, True, False, False, False),
            )
        return fns

    def _bind_gear(self) -> None:
        super()._bind_gear()
        fns = self._gear_fns.get(self._gear_ladder[self._gear].level)
        self._run_to_async = (fns or {}).get("run_to_async")

    def _shift_gear(self, level: int) -> None:
        super()._shift_gear(level)
        self._C_shard = self._gear_ladder[level].capacity
        sh = getattr(self, "_shard_shifter", None)
        # a shard-shifter-initiated shift (_gear_tick_async) already has
        # level == max(levels): the per-shard ladder states PRODUCED the
        # new envelope, so keep them — seeding here would hoist every
        # cool shard to the envelope and clear its downshift streak,
        # reverting to exactly the fleet-wide behavior the shard shifter
        # removes. Only shifts that bypassed it (pressure downshifts,
        # scalar-path shifts, checkpoint restore) need the re-alignment.
        if sh is not None and level != max(sh.levels):
            sh.seed(level)
        # the resize re-materialized the pool off-mesh: re-pin per chip
        self._place_state()

    def _pool_occupancy(self) -> int:
        """Gearing decision signal: live rows on the FULLEST shard."""
        return int(jnp.max(
            jnp.sum(self.state.pool.time != simtime.NEVER, axis=-1)
        ))

    # ---- asynchronous conservative sync (cs/0409032) plumbing ----

    def _refresh_async_args(self) -> None:
        """(Re)build the traced async-kernel inputs from the current
        lookahead spec: per-shard window widths, the in-edge lookahead
        view, and the roughness-suppression spread bound (configured, or
        auto-derived — parallel/lookahead.auto_spread)."""
        spec = self._lookahead
        self._async_runahead = jnp.asarray(
            lookahead_mod.shard_runahead(spec, self.runahead)
        )
        self._async_look_in = jnp.asarray(
            lookahead_mod.in_edge_matrix(spec)
        )
        self._async_spread = jnp.int64(
            self._async_spread_cfg
            or lookahead_mod.auto_spread(spec, self.runahead)
        )
        self._look_in_cache = None  # host copy re-derived on next read

    def _note_async_dispatch(self, ainfo, supersteps: int) -> None:
        frontier, spread_max, steps, yields, blocked = ainfo[:5]
        c = self._async_counters
        c["dispatches"] += 1
        c["supersteps"] += supersteps
        c["shard_windows"] += steps
        c["yields"] += yields
        c["blocked_on_neighbor"] += blocked
        self._async_spread_max = max(self._async_spread_max, spread_max)
        self._async_frontier = frontier
        if len(ainfo) > 5 and ainfo[5] is not None:
            delta = ainfo[5]
            if self._async_shard_stats.shape != delta.shape:
                # elastic relayout resized the mesh mid-run
                self._async_shard_stats = np.zeros_like(delta)
            self._async_shard_stats += delta
        # analytic per-chip frontier-exchange volume: every superstep
        # runs one horizon exchange, plus one f0 exchange per dispatch;
        # each ships one i64 per partner (len(shifts) under ppermute,
        # S under the all_gather arm) — the quantity --mesh-smoke gates
        self._mesh_collective_bytes += (
            (supersteps + 1) * self.exchange_partners * 8
        )

    @property
    def exchange_partners(self) -> int:
        """Collective partners per chip per frontier exchange: the
        compiled ppermute schedule's width, or S for the all_gather arm."""
        if self._exchange == "ppermute":
            return len(self._async_shifts)
        return self.num_shards

    def _place_state(self) -> None:
        """shard_map only: pin every [S, ...] state leaf to its chip
        (parallel/mesh.shard_island_state). Called after any host-side
        relayout — build, gear resize, migration, checkpoint restore —
        so dispatches start chip-resident instead of paying an implicit
        reshard; a no-op under vmap."""
        if getattr(self, "mesh", None) is None:
            return
        from shadow_tpu.parallel import mesh as mesh_mod

        self.state = mesh_mod.shard_island_state(self.state, self.mesh)

    def _ensure_shift_coverage(self) -> None:
        """Safety gate after any assignment change: every finite in-edge
        of the re-derived lookahead must ride a compiled ppermute shift —
        an uncovered edge would silently drop that neighbor's frontier
        bound from the horizon (causality, not perf). A value-only
        rebalance (connectivity preserved — the common case, and what
        min-cut refinement produces) changes nothing; a structural
        change widens the schedule and rebuilds the kernel set once
        (counted in mesh.exchange_rebuilds)."""
        if not self._async or self._exchange != "ppermute":
            return
        req = lookahead_mod.ppermute_shifts(self._lookahead)
        if set(req) <= set(self._async_shifts):
            return
        self._async_shifts = tuple(
            sorted(set(self._async_shifts) | set(req))
        )
        if getattr(self, "_gear_fns", None):
            self._gear_fns = {}
            self._bind_gear()
            self._exchange_rebuilds += 1

    def mesh_stats(self) -> dict[str, int] | None:
        """Multi-chip counters for the metrics registry (schema v11
        `mesh.*`); None on single-shard builds."""
        if self.num_shards <= 1 or not self._async:
            return None
        return {
            "frontier_exchange_bytes": int(self._mesh_collective_bytes),
            "exchange_rebuilds": int(self._exchange_rebuilds),
        }

    def mesh_gauges(self) -> dict | None:
        """Multi-chip gauges (schema v11 `mesh.*`): chip count, the
        neighbor-exchange schedule width vs the in-edge degree, per-chip
        committed-event balance, and the placement's cut cost against
        the block partition's."""
        if self.num_shards <= 1:
            return None
        from shadow_tpu.parallel import balancer as balancer_mod

        ev = np.asarray(jax.device_get(
            self.state.counters.events_committed
        )).reshape(-1)
        deg = lookahead_mod.in_degree(self._lookahead)
        slot = (
            np.asarray(jax.device_get(self.params.slot_of))
            if self.rebalance_enabled
            else np.arange(self.num_hosts)
        )
        Hl = self.num_hosts // self.num_shards
        g = {
            "chips": int(self.num_shards),
            "shard_map": int(self.mode == "shard_map"),
            "exchange_partners": int(self.exchange_partners),
            "in_degree_max": int(deg.max()) if deg.size else 0,
            "events_per_chip_min": int(ev.min()),
            "events_per_chip_max": int(ev.max()),
            "events_per_chip_mean": float(ev.mean()),
            "cut_cost": float(balancer_mod.cut_cost(
                np.asarray(slot) // Hl, self._latency_np,
                self._host_vertex_g,
            )),
            "cut_cost_block": float(balancer_mod.cut_cost(
                lookahead_mod.shard_of_hosts(
                    self.num_hosts, self.num_shards
                ),
                self._latency_np, self._host_vertex_g,
            )),
        }
        return g

    def _gear_tick_async(self, occ_v: np.ndarray) -> bool:
        """Per-shard gearing decision from the async kernel's occupancy
        vector; returns True iff the envelope (compiled tier) changed."""
        if self._shard_shifter is None:
            return False
        if self.pressure is not None and self.pressure.hold_gear:
            return False
        hi = self._gear_ladder[self._gear].hi
        new = self._shard_shifter.observe(
            self._gear, occ_v, press=(occ_v >= hi)
        )
        if new is None:
            return False
        self._shift_gear(new)
        return True

    def async_stats(self) -> dict[str, int] | None:
        """Async-sync counters for the metrics registry (schema v9
        `async.*`); None when the barrier driver is configured."""
        if not self._async:
            return None
        return dict(self._async_counters)

    def async_shard_profile(self) -> dict | None:
        """Per-shard async posture for the profiling recorder
        (obs/prof.py): cumulative steps/yields/blocked per shard, the
        last-fetched frontier surface, and the in-edge lookahead matrix
        (host-cached — no device read on the tick path). None when the
        barrier driver is configured."""
        if not self._async:
            return None
        st = self._async_shard_stats
        p = {
            "shards": int(self.num_shards),
            "steps": [int(x) for x in st[0]],
            "yields": [int(x) for x in st[1]],
            "blocked": [int(x) for x in st[2]],
        }
        if self._async_frontier is not None:
            p["frontier_ns"] = [int(x) for x in self._async_frontier]
        la = self._look_in_cache
        if la is None:
            la = self._look_in_cache = [
                [int(x) for x in row]
                for row in np.asarray(jax.device_get(self._async_look_in))
            ]
        p["lookahead_in"] = la
        return p

    def reset_frontier_spread(self) -> None:
        """Zero the max-observed frontier-spread gauge — phase-windowed
        measurement (bench.py --balance-smoke gates on the spread AFTER
        the balancer had its chance to heal, not the whole-run max that
        the pre-migration transient dominates)."""
        self._async_spread_max = 0

    def async_gauges(self) -> dict[str, int] | None:
        """Async-sync gauges: the spread bound, the maximum observed
        frontier spread, the last dispatch's frontier extent, and the
        per-shard gear envelope."""
        if not self._async:
            return None
        spec = self._lookahead
        g = {
            "spread_bound_ns": int(self._async_spread),
            "frontier_spread_max_ns": int(self._async_spread_max),
            "min_cross_lookahead_ns": (
                int(spec.min_cross)
                if spec.min_cross < int(simtime.NEVER) else -1
            ),
        }
        if self._async_frontier is not None:
            g["frontier_min_ns"] = int(self._async_frontier.min())
            g["frontier_max_ns"] = int(self._async_frontier.max())
        if self._shard_shifter is not None:
            g["gear_level_min"] = int(min(self._shard_shifter.levels))
            g["gear_level_max"] = int(max(self._shard_shifter.levels))
        return g

    def _async_meta(self) -> dict | None:
        """Checkpoint-header async block (core/checkpoint.save): the
        derived bounds and last-observed frontier surface, so an operator
        can audit a resumed run's async posture without replaying it.
        Informational — resume re-derives frontiers from pool state."""
        if not self._async:
            return None
        m = {
            "spread_ns": int(self._async_spread),
            "runahead_ns": [int(x) for x in np.asarray(
                jax.device_get(self._async_runahead))],
        }
        spec = self._lookahead
        if spec.min_cross < int(simtime.NEVER):
            m["min_cross_lookahead_ns"] = int(spec.min_cross)
            m["critical_link"] = list(spec.critical)
        if self._async_frontier is not None:
            m["frontier_ns"] = [int(x) for x in self._async_frontier]
        if self._shard_shifter is not None:
            m["gear_levels"] = [int(x) for x in self._shard_shifter.levels]
        return m

    def _runahead_bound_hint(self) -> str:
        """The derived safe bounds, for runahead-violation errors: the
        minimum cross-shard path latency (the async lookahead) and the
        minimum intra-shard latency — the tighter of the two is the
        largest safe experimental.runahead."""
        spec = self._lookahead
        never = int(simtime.NEVER)
        intra = int(spec.intra.min()) if spec.intra.size else never
        parts = []
        if spec.min_cross < never:
            j, i = spec.critical
            parts.append(
                f"derived minimum cross-shard path latency (the safe "
                f"lookahead) is {int(spec.min_cross)} ns on shard link "
                f"{j}->{i}"
            )
        if intra < never:
            parts.append(f"minimum intra-shard path latency is {intra} ns")
        if not parts:
            return "the topology bakes no finite path latency"
        safe = min(int(spec.min_cross), intra)
        parts.append(f"set experimental.runahead <= {safe} ns")
        return "; ".join(parts)

    # ---- self-balancing plane (parallel/balancer.py) ----

    def attach_balancer(self, balancer) -> None:
        """Arm (or replace) the closed-loop hot-shard controller; needs
        the rebalance-capable kernel (slot_of routing)."""
        if not self.rebalance_enabled:
            raise RuntimeError(
                "attach_balancer needs rebalance=True or balancer=True "
                "at build time (the slot_of routing table compiles in)"
            )
        self.balancer = balancer

    def balance_stats(self) -> dict[str, int] | None:
        """Balancer counters for the metrics registry (schema v10
        `balance.*`); None when no controller is attached."""
        if self.balancer is None:
            return None
        d = self.balancer.stats()
        d["rebalances"] = int(self.rebalances)
        return d

    def balance_gauges(self) -> dict | None:
        if self.balancer is None:
            return None
        return self.balancer.gauges()

    def _balance_meta(self) -> dict | None:
        """Checkpoint-header balance block (core/checkpoint.save): the
        LIVE host→slot assignment plus the controller posture, so a
        drain-to-checkpoint persists a migrated layout auditable without
        replay. Restore rebuilds the routing table from the state's own
        gid rows (_post_restore) — the assignment here is the operator-
        facing record, the controller block is what resume re-arms."""
        if not self.rebalance_enabled:
            return None
        slot = np.asarray(jax.device_get(self.params.slot_of))
        m = {
            "rebalances": int(self.rebalances),
            "assignment": [int(x) for x in slot],
        }
        if self.balancer is not None:
            m["controller"] = self.balancer.meta()
        return m

    def _import_foreign_layout(self, foreign, meta) -> None:
        """checkpoint.restore_relayout hook: adopt a checkpoint taken at
        a DIFFERENT partition (another mesh size, or the global engine)
        into this build — globalize by gid to the canonical order, then
        re-islandize for this partition (identity block assignment; the
        _post_restore hook that follows re-derives slot_of/lookahead
        from the restored rows). Chains/RNG key on global host ids, so
        the resumed run extends the checkpointed chain exactly."""
        live = int(np.sum(
            np.asarray(jax.device_get(foreign.pool.time))
            != simtime.NEVER
        ))
        tmp = globalize_state(foreign, max(live, 1))
        self.state = islandize_state(
            tmp, self.num_shards, self._C_shard
        )
        self._place_state()

    def _post_restore(self, meta: dict) -> None:
        """Re-sync layout-derived runtime state after a checkpoint
        restore (core/checkpoint.restore calls this once the leaves are
        in place): the slot_of routing table and the derived async
        lookahead live OUTSIDE the checkpointed state pytree, but the
        restored host rows carry their layout in state.host.gid — a
        checkpoint taken after a live migration restores the permuted
        rows, so the routing table must be rebuilt from them (without
        this hook, resuming a migrated run silently misroutes every
        cross-shard event against a stale identity table)."""
        if self.rebalance_enabled:
            gid = np.asarray(
                jax.device_get(self.state.host.gid)
            ).reshape(-1)
            slot = np.empty(self.num_hosts, np.int32)
            slot[gid] = np.arange(self.num_hosts, dtype=np.int32)
            self.params = self.params.replace(slot_of=jnp.asarray(slot))
            if self._async:
                self._lookahead = lookahead_mod.derive(
                    self._latency_np, self._host_vertex_g,
                    self.num_shards, assignment=slot,
                )
                self._refresh_async_args()
                self._ensure_shift_coverage()
        if self._shard_shifter is not None:
            # restore the per-shard ladder states the checkpoint header
            # recorded (gearbox.ShardGearShifter.restore); a header
            # without them (pre-v11, or barrier run) seeds flat
            levels = (meta.get("async") or {}).get("gear_levels")
            if not self._shard_shifter.restore(levels, self._gear):
                self._shard_shifter.seed(self._gear)
        self._place_state()
        if self.balancer is not None:
            bm = (meta.get("balance") or {}).get("controller")
            if bm:
                self.balancer.restore_meta(bm)

    # ---- between-window re-sharding (the P3 work-stealing replacement,
    # scheduler_policy_host_steal.c:1-562 / logical_processor.rs:43-54) ----

    def shard_loads(self) -> np.ndarray:
        """[S] resident event rows per shard (pool + host spill)."""
        t = np.asarray(jax.device_get(self.state.pool.time))
        occ = (t != simtime.NEVER).sum(axis=-1)
        sp = getattr(self, "_spill", None)
        if sp is not None:
            occ = occ + np.array(
                [r[0].shape[0] for r in sp._rows]
            )
        return occ

    def host_loads(self) -> np.ndarray:
        """[H] resident event rows per GLOBAL host id (pool + spill, by
        destination) — the per-host load proxy both the LPT rebalance and
        the balancer's min-cut refinement consume."""
        H = self.num_hosts
        sp = self._spill_store()
        pt = np.array(jax.device_get(self.state.pool.time)).reshape(-1)
        pd = np.array(jax.device_get(self.state.pool.dst)).reshape(-1)
        live = pt != simtime.NEVER
        load = np.bincount(pd[live], minlength=H).astype(np.int64)
        for rows in sp._rows:
            if rows[0].shape[0]:
                load += np.bincount(rows[1], minlength=H)
        return load

    def rebalance_now(self) -> None:
        """Permute host→shard assignment to even out resident load.

        Load proxy = events resident per destination host (pool + spill
        histogram). Assignment = LPT greedy onto S bins of exactly H/S
        hosts each. All [H]-leading state permutes host-side (rare, a few
        MB); pool and spill rows re-route to their new owners; the
        slot_of routing table updates in place — no recompilation, and no
        observable effect on results (per-host order, RNG streams and seq
        numbering are functions of the GLOBAL host id only).
        """
        S, Hl = self.num_shards, self.num_hosts // self.num_shards
        H = self.num_hosts
        load = self.host_loads()

        # --- LPT: heaviest host to the lightest non-full shard ---
        order = np.argsort(-load, kind="stable")
        shard_load = np.zeros(S, np.int64)
        shard_fill = np.zeros(S, np.int32)
        new_slot = np.zeros(H, np.int32)
        for h in order:
            open_ = shard_fill < Hl
            cand = np.flatnonzero(open_)
            s = int(cand[np.argmin(shard_load[cand])])
            new_slot[h] = s * Hl + shard_fill[s]
            shard_fill[s] += 1
            shard_load[s] += load[h]
        self._apply_assignment(new_slot)

    def migrate_hosts(self, new_slot) -> None:
        """Apply an EXPLICIT host→slot assignment (the balancer's min-cut
        refinement output, parallel/balancer.py): validated — a
        permutation of range(H) with exactly H/S slots per shard — then
        applied through the same recompile-free permutation seam as
        rebalance_now."""
        S, Hl = self.num_shards, self.num_hosts // self.num_shards
        H = self.num_hosts
        new_slot = np.asarray(new_slot, np.int32)
        if new_slot.shape != (H,) or not np.array_equal(
            np.sort(new_slot), np.arange(H, dtype=np.int32)
        ):
            raise ValueError(
                f"migrate_hosts needs a permutation of range({H}) "
                f"(host -> slot); got shape {new_slot.shape}"
            )
        del S, Hl  # permutation of range(H) implies H/S slots per shard
        self._apply_assignment(new_slot)

    def _balance_snapshot(self):
        """Rollback point for a verify-then-commit migration: state and
        params are immutable pytrees (references suffice); the spill
        store and lookahead spec mutate, so they are copied."""
        sp = self._spill_store()
        return {
            "state": self.state,
            "params": self.params,
            "spill_rows": [tuple(r) for r in sp._rows],
            "spill_partial_min": list(sp._partial_min),
            "spill_drained": sp.drained_total,
            "lookahead": self._lookahead,
            "rebalances": self.rebalances,
        }

    def _balance_rollback(self, snap) -> None:
        """Restore the pre-migration layout (mid-migration failure or
        digest divergence — parallel/balancer.py): the pre-move pytrees
        re-bind wholesale, the spill store's rows roll back, and the
        async traced inputs re-derive for the restored assignment."""
        self.state = snap["state"]
        self.params = snap["params"]
        sp = self._spill_store()
        sp._rows = [tuple(r) for r in snap["spill_rows"]]
        sp._partial_min = list(snap["spill_partial_min"])
        sp.drained_total = snap["spill_drained"]
        self._lookahead = snap["lookahead"]
        self.rebalances = snap["rebalances"]
        if self._async:
            self._refresh_async_args()
        if self._shard_shifter is not None:
            self._shard_shifter.seed(self._gear)
        self._place_state()

    def _apply_assignment(self, new_slot: np.ndarray) -> None:
        """The permutation seam shared by rebalance_now (LPT) and
        migrate_hosts (balancer refinement): permute host-indexed state,
        re-route pool + spill rows to their new owner shards, update the
        slot_of routing table, and re-derive the traced async lookahead —
        never a recompile."""
        if not self.rebalance_enabled:
            raise RuntimeError(
                "rebalance_now()/migrate_hosts() need rebalance=True (or "
                "balancer=True) at build time: the window kernel must "
                "compile slot_of-table routing, or the permuted layout "
                "would silently misroute events"
            )
        S, Hl = self.num_shards, self.num_hosts // self.num_shards
        H = self.num_hosts
        sp = self._spill_store()
        new_slot = np.asarray(new_slot, np.int32)

        # --- permute every [S, Hl, ...] host-indexed leaf ---
        gid = np.array(jax.device_get(self.state.host.gid)).reshape(-1)
        cur_slot = np.empty(H, np.int32)
        cur_slot[gid] = np.arange(H, dtype=np.int32)
        # row j of the NEW layout holds the host whose new_slot == j
        host_at_new = np.empty(H, np.int32)
        host_at_new[new_slot] = np.arange(H, dtype=np.int32)
        idx = cur_slot[host_at_new]  # new flat row j ← old flat row idx[j]

        def perm(x):
            x = np.array(jax.device_get(x))
            flat = x.reshape((H,) + x.shape[2:])
            return jnp.asarray(flat[idx].reshape(x.shape))

        self.state = self.state.replace(
            host=jax.tree.map(perm, self.state.host),
            subs=jax.tree.map(
                lambda x: perm(x) if getattr(x, "ndim", 0) >= 2
                and x.shape[0] == S and x.shape[1] == Hl else x,
                self.state.subs,
            ),
            obs=(
                self.state.obs.replace(
                    host_events=perm(self.state.obs.host_events),
                    host_last_t=perm(self.state.obs.host_last_t),
                    host_digest=perm(self.state.obs.host_digest),
                )
                if self.state.obs is not None
                else None
            ),
            flight=(
                jax.tree.map(perm, self.state.flight)
                if self.state.flight is not None
                else None
            ),
            rng_keys=perm(self.state.rng_keys),
        )

        # --- re-route pool + spill rows to their new owner shards ---
        cols = [
            np.array(jax.device_get(c)) for c in (
                self.state.pool.time, self.state.pool.dst,
                self.state.pool.src, self.state.pool.seq,
                self.state.pool.kind, self.state.pool.payload,
            )
        ]
        C_s = cols[0].shape[1]
        flatc = [c.reshape((-1,) + c.shape[2:]) for c in cols]
        livef = flatc[0] != simtime.NEVER
        allrows = [c[livef] for c in flatc]
        for rows in sp._rows:
            if rows[0].shape[0]:
                allrows = [
                    np.concatenate([a, r]) for a, r in zip(allrows, rows)
                ]
        owner = new_slot[allrows[1]] // Hl
        t_new = np.full((S, C_s), simtime.NEVER, np.int64)
        o_new = [np.zeros((S, C_s) + c.shape[1:], c.dtype)
                 for c in allrows[1:]]
        sp._rows = [sp._empty() for _ in range(S)]
        # the partial-residency clamps describe the OLD layout; reset so a
        # stale minimum cannot clamp future windows (manage recomputes per
        # rebalance)
        sp._partial_min = [int(simtime.NEVER)] * S
        for s in range(S):
            rows = np.where(owner == s)[0]
            # earliest rows stay on device; overflow goes to the spill
            # tier (never dropped)
            osort = rows[HostSpill._order(
                allrows[0][rows], allrows[1][rows],
                allrows[2][rows], allrows[3][rows],
            )]
            fill = self._spill_marks()[1]
            keep, rest = osort[:fill], osort[fill:]
            n = keep.shape[0]
            t_new[s, :n] = allrows[0][keep]
            for c_new, c in zip(o_new, allrows[1:]):
                c_new[s, :n] = c[keep]
            if rest.shape[0]:
                sp._rows[s] = tuple(
                    c[rest] for c in allrows
                )
                sp.drained_total += rest.shape[0]
        from shadow_tpu.core.state import EventPool

        self.state = self.state.replace(pool=EventPool(
            time=jnp.asarray(t_new), dst=jnp.asarray(o_new[0]),
            src=jnp.asarray(o_new[1]), seq=jnp.asarray(o_new[2]),
            kind=jnp.asarray(o_new[3]), payload=jnp.asarray(o_new[4]),
        ))
        self.params = self.params.replace(
            slot_of=jnp.asarray(new_slot)
        )
        self.rebalances += 1
        if self._async:
            # the permuted host->shard assignment changes which latencies
            # bound each shard pair; re-derive (traced inputs — the
            # compiled async kernel is untouched)
            self._lookahead = lookahead_mod.derive(
                self._latency_np, self._host_vertex_g, self.num_shards,
                assignment=new_slot,
            )
            self._refresh_async_args()
            self._ensure_shift_coverage()
        if self._shard_shifter is not None:
            # per-shard occupancies just shuffled wholesale: the per-shard
            # ladder states describe the OLD layout — re-align to the
            # bound envelope (a bypass shift, like checkpoint restore)
            self._shard_shifter.seed(self._gear)
        self._place_state()

    def _maybe_rebalance(self) -> None:
        """Skew trigger: rebalance when the heaviest shard holds 2x the
        mean resident load (and enough rows for the skew to matter)."""
        if not self.rebalance_enabled:
            return
        occ = self.shard_loads()
        mean = occ.mean()
        if mean > 0 and occ.max() > max(2 * mean, occ.min() + 256):
            self.rebalance_now()

    def _run_to_halves(self, stop_at, wpd):
        """(issue_fn, fetch_fn) halves of one fused islands dispatch —
        the async per-shard-frontier loop or the barrier loop. issue
        enqueues the device program (futures only); fetch performs every
        blocking host read, once, in one place (the old thunk fetched
        the window count twice). Supervised retries re-run both halves,
        re-reading bound kernels and re-clamping the spill stop."""

        def issue(stop_at=stop_at, wpd=wpd):
            # per-attempt clamp: a pressure rung may have engaged the
            # spill tier since the driver computed stop_at
            stop_at, wpd = self._live_spill_clamp(stop_at, wpd)
            if self._async:
                return self._run_to_async(
                    self.state, self.params,
                    self._async_runahead, self._async_look_in,
                    self._async_spread, stop_at, wpd,
                )
            return self._run_to(self.state, self.params, stop_at, wpd)

        def fetch(out):
            if self._async:
                st, mn, press, occ, w, fr, sp, stp, yld, blk = out
                stp_v = np.asarray(jax.device_get(stp)).reshape(-1)
                yld_v = np.asarray(jax.device_get(yld)).reshape(-1)
                blk_v = np.asarray(jax.device_get(blk)).reshape(-1)
                extra = (
                    np.asarray(jax.device_get(fr)).reshape(-1),
                    int(np.max(np.asarray(jax.device_get(sp)))),
                    int(stp_v.sum()),
                    int(yld_v.sum()),
                    int(blk_v.sum()),
                    # per-shard [3, S] deltas for the profiling plane
                    np.stack([stp_v, yld_v, blk_v]).astype(np.int64),
                )
            else:
                st, mn, press, occ, w = out
                extra = None
            return (
                st,
                int(np.min(np.asarray(jax.device_get(mn)))),
                bool(np.max(np.asarray(jax.device_get(press)))),
                np.asarray(jax.device_get(occ)).reshape(-1),
                int(np.max(np.asarray(jax.device_get(w)))),
                extra,
            )

        return issue, fetch

    def run(self, until=None, windows_per_dispatch: int = 64) -> None:
        from shadow_tpu.core import spill as spill_mod
        from shadow_tpu.obs import metrics as metrics_mod

        stop = self.stop_time if until is None else min(until, self.stop_time)
        spill = self._spill_store()
        obs = self.obs_session
        pipe = self._pipeline()
        last = None
        try:
            while True:
                if (
                    (last is not None and last[2]) or spill.count
                    or self._force_spill  # injected force_spill fault
                ):
                    if pipe is not None:
                        # rebalance + spill manage mutate the layout /
                        # pool: a barrier point (already tallied as a
                        # forced drain when speculation was skipped)
                        pipe.close()
                    with metrics_mod.span(obs, "spill"):
                        self._maybe_rebalance()
                        stop_at = spill_mod.manage(self, spill, stop)
                else:
                    stop_at = stop
                # single-window dispatches while the spill is active
                # (exactness requires a manage pass between windows —
                # core/spill.py)
                wpd = 1 if spill.count else windows_per_dispatch
                if self._fault_plane_active():
                    # hand off at the next injection/checkpoint mark
                    stop_at = min(stop_at, self._fault_mark())
                # adopt the issued-ahead dispatch iff the committed state
                # and recomputed args match (core/pipeline.py)
                pending = (
                    pipe.take(self.state, (stop_at, wpd))
                    if pipe is not None else None
                )
                if pending is None:
                    with metrics_mod.span(obs, "dispatch", windows=wpd):
                        p = self._sv_issue(
                            "run_to", *self._run_to_halves(stop_at, wpd)
                        )
                        (self.state, mn, press, occ_v, w,
                         ainfo) = self._sv_await(p)
                else:
                    with metrics_mod.span(obs, "await", windows=wpd):
                        (self.state, mn, press, occ_v, w,
                         ainfo) = self._sv_await(pending)
                occ = int(occ_v.max())
                # two-slot pipeline: issue the next fused dispatch before
                # draining this handoff — the mesh computes its next
                # supersteps while the host drains; balancer migrations,
                # fault drains and gear shifts stay barrier points (the
                # invalidate below discards on any state mutation)
                if pipe is not None and mn < stop:
                    if (not press and not spill.count
                            and not self._force_spill
                            and self._handoff_quiet(mn)
                            and not self._sv_disrupted()):
                        nxt = stop
                        if self._fault_plane_active():
                            nxt = min(nxt, self._fault_mark())
                        with metrics_mod.span(
                            obs, "issue", windows=windows_per_dispatch
                        ):
                            pipe.put(
                                self._sv_issue(
                                    "run_to",
                                    *self._run_to_halves(
                                        nxt, windows_per_dispatch
                                    ),
                                ),
                                self.state,
                                (nxt, windows_per_dispatch),
                            )
                    else:
                        pipe.forced_drain()
                with metrics_mod.span(obs, "host_drain"):
                    self._gear_note_dispatch()
                    self.windows_run += w
                    if ainfo is not None:
                        self._note_async_dispatch(ainfo, w)
                    if obs is not None:
                        obs.round_done(self, mn)
                    self._audit_tick(mn)
                    # gearing: a red-zone early exit upshifts (one pool
                    # re-sort) before the spill tier would pay host drain
                    # round-trips; under async the decision is PER SHARD
                    # from the occupancy vector (gearbox.ShardGearShifter),
                    # each shard's ladder state advancing at its own
                    # dispatch boundary
                    if self._async and self._shard_shifter is not None:
                        shifted = self._gear_tick_async(occ_v)
                    else:
                        shifted = self._gear_tick(occ, press=press)
                    if self._fault_plane_active():
                        self._handoff_tick(mn)
                    if self.balancer is not None:
                        # closed-loop hot-shard healing (parallel/
                        # balancer.py): detection from the dispatch's own
                        # occupancy vector + frontier surface; a committed
                        # migration permutes the layout through the
                        # traced-lookahead seam (no recompile)
                        if self.balancer.observe(
                            self, occ_v,
                            ainfo[0] if ainfo is not None else None,
                        ):
                            shifted = True
                    self._run_handoff_hooks(mn)
                if pipe is not None:
                    if self._sv_disrupted():
                        pipe.discard()
                    else:
                        pipe.invalidate(self.state)
                if mn >= stop and spill.min_time >= stop and not press:
                    break
                if self.elastic is not None:
                    # elastic re-expansion probe (parallel/elastic.py):
                    # may raise MeshReexpand at this committed boundary —
                    # the runner drains and relayouts onto the recovered
                    # mesh
                    self.elastic.on_dispatch(self, mn)
                fr_min = int(ainfo[0].min()) if ainfo is not None else None
                cur = (mn, spill.count, press, fr_min)
                if cur == last and mn >= stop_at and not shifted:
                    cap = self._gear_ladder[self._gear].capacity
                    if self._pressure_stall(window=mn, occupancy=occ,
                                            capacity=cap):
                        last = None  # a ladder rung reshaped the tier
                        continue
                    raise self._pool_exhausted(
                        "spill tier cannot make progress (single over-full "
                        "timestamp or no pool headroom for one window's "
                        "emissions); raise experimental.event_capacity",
                        window=mn, occupancy=occ, capacity=cap,
                    )
                elif self.pressure is not None:
                    self.pressure.note_progress()
                last = cur
        finally:
            if pipe is not None:
                pipe.close()

    def run_stepwise(self, until=None) -> int:
        from shadow_tpu.core import spill as spill_mod
        from shadow_tpu.obs import metrics as metrics_mod

        stop = self.stop_time if until is None else min(until, self.stop_time)
        spill = self._spill_store()
        obs = self.obs_session
        windows = 0
        stall = 0
        # committed frontier carried from the dispatch's own return value
        # (a fresh per-iteration jnp.min dispatched one tiny reduce kernel
        # per window for nothing); None = derive from the pool
        min_next = None
        while True:
            if self._shifter is not None:
                # gear decision BEFORE spill manage: an upshift absorbs
                # red-zone pressure without a host drain episode
                self._gear_tick(self._pool_occupancy())
            with metrics_mod.span(obs, "spill"):
                tok = self.state
                stop_at = spill_mod.manage(self, spill, stop)
            if self.state is not tok or min_next is None:
                min_next = int(jax.device_get(jnp.min(self.state.pool.time)))
            if self._fault_plane_active():
                tok = self.state
                self._handoff_tick(min_next)
                if self.state is not tok:
                    # a drain may have removed the frontier event
                    min_next = int(
                        jax.device_get(jnp.min(self.state.pool.time))
                    )
            if min_next >= stop_at:
                if min_next >= stop and spill.min_time >= stop:
                    break
                stall += 1
                if stall > 2:
                    occ = self._pool_occupancy()
                    cap = self._gear_ladder[self._gear].capacity
                    if self._pressure_stall(window=min_next, occupancy=occ,
                                            capacity=cap):
                        stall = 0  # a ladder rung reshaped the tier
                        continue
                    raise self._pool_exhausted(
                        "spill tier cannot make progress (single over-full "
                        "timestamp or no pool headroom for one window's "
                        "emissions); raise experimental.event_capacity",
                        window=min_next, occupancy=occ, capacity=cap,
                    )
                continue
            stall = 0
            if self.pressure is not None:
                self.pressure.note_progress()
            ws = min_next
            clamp = int(jax.device_get(
                jnp.min(self.state.exch_deferred_min)
            ))
            we = min(ws + self.runahead, stop_at, clamp)
            with metrics_mod.span(obs, "dispatch", windows=1):

                def _dispatch(ws=ws, we=we):
                    we, _ = self._live_spill_clamp(we, 1)
                    st, mn = self._step(
                        self.state, self.params, ws, max(ws, we)
                    )
                    return st, int(np.min(np.asarray(jax.device_get(mn))))

                self.state, mn = self._sv("step", _dispatch)
            self._gear_note_dispatch()
            min_next = mn
            if self._audit_active():
                self._audit_tick(mn)
            # host-drain contract parity with the fused driver: handoff
            # hooks (sharded ones drain through the multi-worker host
            # plane, core/hostplane.py) run at every stepwise boundary
            self._run_handoff_hooks(mn)
            windows += 1
            self.windows_run += 1
        return windows

    def suggest_exchange_slots(self) -> dict[str, int | float]:
        """Runtime-informed X sizing (VERDICT r4 #2): from the observed
        exchange traffic of THIS run, compute the X a rebuild should use.

        avg rows per (src, dst, window) = exchange_sent / (windows·S·(S−1));
        the suggestion is 2× that (headroom for wave clustering) with the
        auto-sizing floor of 64. Changing X changes compiled shapes, so
        apply it by rebuilding — the intended loop is: short calibration
        run, read the suggestion, rebuild for the long run.
        """
        S = self.num_shards
        c = self.counters()
        sent, deferred = c["exchange_sent"], c["exchange_deferred"]
        w = max(self.windows_run, 1)
        avg = sent / (w * S * max(S - 1, 1))
        return {
            "exchange_slots": self.exchange_slots,
            "suggested": max(64, int(2 * avg) + 1),
            "avg_rows_per_pair_per_window": round(avg, 2),
            "windows": self.windows_run,
            "exchange_sent": sent,
            "exchange_deferred": deferred,
            "defer_ratio": round(deferred / max(sent + deferred, 1), 4),
        }

    def _ensure_optimistic(self):
        """Lazily compile the speculative SUB-STEP kernel (a second XLA
        program): the conservative kernel stays untouched, so conservative
        runs never pay for the done_t checks.

        The attempt loop is HOST-DRIVEN (one dispatch per sub-step, like
        run_stepwise) rather than a fused on-device while_loop: compiling
        vmap(S) of while_loop(full netstack step) measured >90 min on a
        CPU host at S=8 — the fused program buys one dispatch per attempt
        but costs a pathological compile. The sub-step kernel is the same
        size as the conservative step (known-fast compile), semantics are
        identical (each sub-step processes [max(mn, ws), we) and reports
        the pmin'd frontier + earliest violation), and the host loop gets
        stall detection for free."""
        if self._attempt is not None:
            return
        spec = self._gear_ladder[self._gear]
        spec_opt = self._island_spec._replace(optimistic=True)
        step_opt = self._step_builder(spec_opt, spec.K)
        # one pmin each inside (make_shard_substep): the shards agree on
        # the frontier + earliest violation, so every shard reports the
        # same scalars
        substep = make_shard_substep(step_opt)

        # cache per gear: a shift rebinds _attempt to the new gear's entry
        # (None until this runs again for that gear)
        self._attempt = self._gear_fns[spec.level]["attempt"] = self._wrap(
            substep, 2
        )

    def run_optimistic(
        self,
        until: int | None = None,
        window_factor: int = 8,
        adaptive: bool = True,
    ) -> tuple[int, int]:
        """Optimistic synchronization ON the islands runner (VERDICT r4
        #4; reference window machinery: controller.c:390-422).

        Same Time-Warp shape as the global engine's run_optimistic —
        speculate [ws, ws + factor·runahead), sub-step to completion,
        roll the WHOLE window back on violation (pure arrays: rollback =
        dropping the speculated pytree on every shard) — with the two
        cross-shard pieces the global engine doesn't need:

          * violation detection: LOCAL-dst emissions check against the
            shard's own done_t at the merge; FOREIGN emissions are
            checked at ARRIVAL on the destination shard, right after the
            all_to_all they already ride (engine.assemble arrival_min) —
            so detection needs no extra collective, and the per-shard
            xmit_min signals combine with ONE pmin per sub-step;
          * the safe retreat width: a conservative-runahead window is
            only violation-free up to the exchange-backpressure clamp
            (an in-transit deferred row at T must not be overtaken), so
            the shrink floor is min(ws + runahead, exch_deferred_min);
            when that floor collapses to ws, one NULL conservative
            window retries the exchange (delivering the earliest
            deferred row — X >= 1 guarantees it) and speculation
            resumes.

        Returns (windows_committed, rollbacks); results match the
        conservative schedule bit-for-bit (tests/test_optimistic.py
        islands gates, vmap and shard_map).
        """
        self._ensure_optimistic()
        spill = self._spill_store()
        if spill.count:
            raise RuntimeError(
                "optimistic islands cannot start with an active spill "
                "tier (speculation has no manage() barrier); drain first "
                "or raise experimental.event_capacity"
            )
        stop = self.stop_time if until is None else min(until, self.stop_time)
        cons = self.runahead
        windows = rollbacks = 0
        factor = window_factor
        streak = 0
        S = self.num_shards
        Hl = self.num_hosts // S
        neg1 = jnp.full((S, Hl), -1, dtype=jnp.int64)
        self.state = self.state.replace(
            host=self.state.host.replace(done_t=neg1)
        )
        from shadow_tpu.obs import counters as obs_mod
        from shadow_tpu.obs import metrics as metrics_mod

        obs = self.obs_session
        min_next = int(jax.device_get(jnp.min(self.state.pool.time)))
        while min_next < stop:
            if self._shifter is not None:
                # margin=2: a speculative window absorbs several windows'
                # inflow between decision points (core/gearbox.target_level);
                # a shift rebinds _attempt to None, so re-ensure per gear
                self._gear_tick(self._pool_occupancy(), margin=2)
                self._ensure_optimistic()
            ws = min_next
            clamp = int(jax.device_get(
                jnp.min(self.state.exch_deferred_min)
            ))
            floor = min(ws + cons, clamp)
            if floor <= ws:
                # in-transit deferred row parked AT the frontier: null
                # conservative window to retry the exchange
                with metrics_mod.span(obs, "dispatch", null_window=1):

                    def _null(ws=ws):
                        st, mn = self._step(
                            self.state, self.params, ws, ws
                        )
                        return st, int(
                            np.min(np.asarray(jax.device_get(mn)))
                        )

                    self.state, min_next = self._sv("step", _null)
                self.state = obs_mod.bump_win(
                    self.state, obs_mod.WIN_OPT_STALLS
                )
                self.windows_run += 1  # one exchange round dispatched
                continue
            # never past stop (the conservative schedule's end), even when
            # the floor itself sits beyond it (then the [ws, stop) window
            # is narrower than the safe width — trivially violation-free)
            we = min(max(min(ws + factor * cons, stop), floor), stop)
            base = self.state  # rollback snapshot (done_t already reset)
            rb0 = rollbacks
            shrinks = 0
            never = int(simtime.NEVER)
            # reshaping pressure rungs are unsafe while `base` pins the
            # compiled shapes (core/pressure.py)
            self._pressure_reshape_ok = False
            while True:  # attempt [ws, we); shrink on violation
                # host-driven sub-step loop (see _ensure_optimistic): one
                # dispatch per sub-step until the window completes or a
                # shard reports a violation
                st = base
                mn_i, viol, k = ws, never, 0
                while mn_i < we and viol >= never:
                    if k >= _MAX_SUBSTEPS:
                        if mn_i <= ws:
                            # mid-attempt: no reshaping rung is safe
                            # (the snapshot pins the compiled shapes) —
                            # typed exhaustion, never a bare RuntimeError
                            raise self._pool_exhausted(
                                "optimistic attempt cannot make progress "
                                "(pool-headroom stall: the window commits "
                                "nothing and its frontier is frozen); "
                                "raise experimental.event_capacity",
                                window=ws,
                                occupancy=self._pool_occupancy(),
                                capacity=self._gear_ladder[
                                    self._gear].capacity,
                            )
                        # genuinely enormous window: shrink to the
                        # reached frontier, retry from the snapshot
                        break
                    with metrics_mod.span(obs, "dispatch"):

                        def _dispatch(st=st, lo=max(mn_i, ws), we=we):
                            s2, mn, vl = self._attempt(
                                st, self.params, lo, we
                            )
                            return (
                                s2,
                                int(np.min(np.asarray(
                                    jax.device_get(mn)
                                ))),
                                int(np.min(np.asarray(
                                    jax.device_get(vl)
                                ))),
                            )

                        st, mn_i, viol = self._sv("attempt", _dispatch)
                        self._gear_note_dispatch()
                    k += 1
                if viol >= never and mn_i < we and k >= _MAX_SUBSTEPS:
                    we = mn_i
                    shrinks += 1
                    continue
                if viol < never and we <= floor:
                    # A floor-width window is violation-free BY CONSTRUCTION
                    # (floor = min(ws + runahead, exchange clamp): emissions
                    # land at or after ws + runahead, and no shard overtakes
                    # an in-transit deferred row). A violation here means
                    # the conservative-width invariant itself is broken —
                    # committing would silently accept a causally-violated
                    # window (ADVICE round-5 finding).
                    raise RuntimeError(
                        f"speculation violation at t={viol} inside a "
                        f"floor-width window [{ws}, {we}) (floor {floor}): "
                        f"the conservative-width invariant is broken — "
                        f"runahead {cons} ns exceeds a real path latency "
                        f"({self._runahead_bound_hint()}), or a handler "
                        f"emitted into the past; refusing to commit"
                    )
                if viol >= never or we <= floor:
                    break
                rollbacks += 1
                shrinks += 1
                if obs is not None and obs.tracer:
                    obs.tracer.instant("rollback", viol_ns=viol)
                we = min(max(viol, floor), stop)
            self._pressure_reshape_ok = True
            if self.pressure is not None:
                self.pressure.note_progress()
            # exchange rounds of the ACCEPTED attempt only: rolled-back
            # sub-steps' exchange counters are discarded with the rollback,
            # and suggest_exchange_slots normalizes sent/windows_run
            self.windows_run += k
            st = obs_mod.bump_win(st, obs_mod.WIN_ROLLBACKS, rollbacks - rb0)
            st = obs_mod.bump_win(st, obs_mod.WIN_SHRINKS, shrinks)
            self.state = st.replace(host=st.host.replace(done_t=neg1))
            min_next = mn_i
            windows += 1
            if obs is not None:
                obs.round_done(self, min_next)
            self._audit_tick(min_next)
            if self._fault_plane_active():
                self._handoff_tick(min_next)
                min_next = int(jax.device_get(jnp.min(self.state.pool.time)))
            # host-drain contract parity with the conservative driver:
            # handoff hooks (sharded ones fan out across the host plane's
            # pinned workers with the canonical (vt, gid) merge) run at
            # every optimistic commit boundary
            self._run_handoff_hooks(min_next)
            if adaptive:
                factor, streak = self.adapt_window_factor(
                    factor, streak, rollbacks > rb0, window_factor
                )
        return windows, rollbacks

    def counters(self) -> dict[str, int]:
        c = jax.device_get(self.state.counters)
        return {
            f.name: int(np.sum(np.asarray(getattr(c, f.name))))
            for f in dataclasses.fields(c)
        }

    def host_trackers(self) -> dict[str, np.ndarray]:
        sub = self.state.subs.get("nic")
        if sub is None:
            return {}
        return {
            k: deislandize_host_array(jax.device_get(getattr(sub, k)))
            for k in ("tx_packets", "tx_bytes", "rx_packets", "rx_bytes")
        }
