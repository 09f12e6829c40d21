"""The batched PDES window kernel and simulation driver.

Reference execution model (src/main/core/manager.c:543-577,
scheduler/scheduler.c:77-94, controller.c:390-422): time advances in
conservative windows bounded by the minimum topology latency ("runahead");
within a window each worker pops its hosts' events in deterministic order
(time, dst, src, seq — event.c:109-152) and runs them; a barrier plus a
min-next-event-time reduction ends the round.

TPU-first re-architecture (one jitted pure function per window):

1. SORT — one sort of the event pool by (dst, time, src, seq) groups this
   window's events into consecutive per-host runs. This replaces all
   per-host priority queues and their locks.
2. MICRO-STEP LOOP — a `lax.while_loop` whose body processes AT MOST ONE
   event per host, fully vectorized across all hosts: candidate = key-min of
   (run head at a per-host cursor, self-inbox); handlers apply masked SoA
   updates. Per-host event order is preserved exactly; hosts are
   data-parallel, which is the same parallelism the reference exploits with
   worker threads (P1 in SURVEY.md §2.5) — but over lanes instead of
   pthreads.
3. The conservative-window invariant (window length ≤ min path latency,
   controller.c:125-153) guarantees cross-host emissions land at or after
   window end, so only SELF-emissions (short timers, NIC refills) can need
   intra-window processing — they go to a small per-host inbox. Everything
   else accumulates in a per-host outbox (no scatter collisions).
4. MERGE — unconsumed sorted rows + outbox + inbox leftovers merge into the
   next pool with one sort by time, truncating to capacity (drops counted).
   The next window start is the min pool time — the reference's min-reduce
   barrier (worker.c:332-363) becomes a jnp.min.

Everything is sorts, gathers, and elementwise selects: XLA scatters
serialize element-by-element on TPU and are banned from this module.

The whole multi-window run can itself be a `lax.while_loop` on device
(`Simulation.run_compiled`), so a complete simulation is ONE XLA program.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from shadow_tpu.core import gearbox
from shadow_tpu.core import hostplane as hostplane_mod
from shadow_tpu.core import pipeline as pipeline_mod
from shadow_tpu.core import pressure as pressure_mod
from shadow_tpu.core.supervisor import PendingDispatch
from shadow_tpu.core import rng as rng_mod
from shadow_tpu.core import simtime, soa
from shadow_tpu.core import spill as spill_mod
from shadow_tpu.obs import audit as audit_mod
from shadow_tpu.obs import counters as obs_mod
from shadow_tpu.obs import flight as flight_mod
from shadow_tpu.obs import metrics as metrics_mod
from shadow_tpu.core.state import (
    PAYLOAD_WORDS,
    Counters,
    EventPool,
    HostState,
    NetParams,
    SimState,
    make_host_state,
)

NEVER = simtime.NEVER


# ---------------------------------------------------------------------------
# Event view + emission interface for handlers
# ---------------------------------------------------------------------------


@struct.dataclass
class EventView:
    """The (at most one) event each host is processing this micro-step.

    All arrays are [H]-indexed; the destination host of event i IS host i.
    ``mask`` is set per handler: valid event AND kind match.
    """

    mask: jnp.ndarray  # [H] bool
    time: jnp.ndarray  # [H] i64
    src: jnp.ndarray  # [H] i32
    seq: jnp.ndarray  # [H] i32
    kind: jnp.ndarray  # [H] i32
    payload: jnp.ndarray  # [H, P] i32


class Emission(NamedTuple):
    mask: jnp.ndarray  # [H] bool — which hosts emit
    time: jnp.ndarray  # [H] i64
    dst: jnp.ndarray  # [H] i32
    kind: jnp.ndarray  # [H] i32 (may be per-host)
    payload: jnp.ndarray  # [H, P] i32


class Emitter:
    """Collects handler emissions; the engine routes them (inbox/outbox)
    in collection order, which fixes the per-source sequence numbering."""

    def __init__(self):
        self.records: list[Emission] = []

    def emit(self, mask, time, dst, kind, payload):
        kind = jnp.broadcast_to(jnp.asarray(kind, jnp.int32), mask.shape)
        self.records.append(
            Emission(mask, time.astype(jnp.int64), dst.astype(jnp.int32), kind, payload)
        )


# handler(state, ev, emitter, params) -> state
Handler = Callable[[SimState, EventView, Emitter, NetParams], SimState]


@struct.dataclass
class MatrixEventView:
    """A whole window of same-kind events per host, [H, K]-shaped, for the
    vectorized fast path (engine `run_matrix`). Column order is per-host
    key order; mask marks real events."""

    mask: jnp.ndarray  # [H, K] bool
    time: jnp.ndarray  # [H, K] i64
    src: jnp.ndarray  # [H, K] i32
    seq: jnp.ndarray  # [H, K] i32
    payload: jnp.ndarray  # [H, K, P] i32


class MatrixRecord(NamedTuple):
    mask: jnp.ndarray  # [H, K] bool
    time: jnp.ndarray  # [H, K] i64
    dst: jnp.ndarray  # [H, K] i32
    kind: jnp.ndarray  # [H, K] i32
    payload: jnp.ndarray  # [H, K, P] i32


class MatrixEmitter:
    """Collects [H, K]-shaped emissions from a matrix handler; the engine
    assigns per-source sequence numbers in (column-major, record-minor)
    order — identical to the loop path's per-event emission order."""

    def __init__(self):
        self.records: list[MatrixRecord] = []

    def emit(self, mask, time, dst, kind, payload):
        kind = jnp.broadcast_to(jnp.asarray(kind, jnp.int32), mask.shape)
        self.records.append(
            MatrixRecord(
                mask, time.astype(jnp.int64), dst.astype(jnp.int32), kind,
                payload,
            )
        )


def draw_uniform(state: SimState, mask):
    """One deterministic uniform draw per masked host; bumps draw counters
    only where masked (so inactive hosts' streams don't advance — matching a
    per-host sequential RNG)."""
    u = rng_mod.uniform_per_host(state.rng_keys, state.host.rng_counter)
    new_c = jnp.where(mask, state.host.rng_counter + 1, state.host.rng_counter)
    state = state.replace(host=state.host.replace(rng_counter=new_c))
    return state, u


# ---------------------------------------------------------------------------
# Window data structures
# ---------------------------------------------------------------------------


@struct.dataclass
class _Inbox:
    time: jnp.ndarray  # [H, B] i64
    src: jnp.ndarray
    seq: jnp.ndarray
    kind: jnp.ndarray
    payload: jnp.ndarray  # [H, B, P]

    @classmethod
    def empty(cls, H, B, PP):
        # payload PACKED (soa.pack_words): PP i64 columns, halving the
        # box-write traffic and the merge-sort operand count
        return cls(
            time=jnp.full((H, B), NEVER, dtype=jnp.int64),
            src=jnp.zeros((H, B), dtype=jnp.int32),
            seq=jnp.zeros((H, B), dtype=jnp.int32),
            kind=jnp.zeros((H, B), dtype=jnp.int32),
            payload=jnp.zeros((H, B, PP), dtype=jnp.int64),
        )


@struct.dataclass
class _Outbox:
    time: jnp.ndarray  # [H, O] i64
    dst: jnp.ndarray
    src: jnp.ndarray
    seq: jnp.ndarray
    kind: jnp.ndarray
    payload: jnp.ndarray  # [H, O, P]
    count: jnp.ndarray  # [H] i32

    @classmethod
    def empty(cls, H, O, PP):
        return cls(
            time=jnp.full((H, O), NEVER, dtype=jnp.int64),
            dst=jnp.zeros((H, O), dtype=jnp.int32),
            src=jnp.zeros((H, O), dtype=jnp.int32),
            seq=jnp.zeros((H, O), dtype=jnp.int32),
            kind=jnp.zeros((H, O), dtype=jnp.int32),
            payload=jnp.zeros((H, O, PP), dtype=jnp.int64),
            count=jnp.zeros((H,), dtype=jnp.int32),
        )


class _DenseWindow(NamedTuple):
    """Dense per-host window matrix: column j holds host h's j-th in-window
    event in (time, src, seq) key order; unused cells carry time NEVER.
    Shapes [H, Kc] (payload [H, Kc, P])."""

    time: jnp.ndarray
    src: jnp.ndarray
    seq: jnp.ndarray
    kind: jnp.ndarray
    payload: jnp.ndarray


class _Tail(NamedTuple):
    """Rows not extracted into the dense matrix: out-of-window events,
    per-host deferred leftovers (rank >= Kc), and spent filler rows (time
    NEVER). Flat [N - H*Kc] arrays; payload is a list of packed word
    columns, as the merge takes them."""

    time: jnp.ndarray
    src: jnp.ndarray
    seq: jnp.ndarray
    kind: jnp.ndarray
    dst: jnp.ndarray
    payload: list


# window-relative time field width in the packed sort key: 2^44 ns ≈ 4.9 h
# bounds a single window's span (runahead), far beyond any real runahead
_DT_BITS = 44
_DT_MAX = (1 << _DT_BITS) - 1
_LO32 = 0xFFFFFFFF


def _pack2(hi, lo):
    """Two int32 columns in one int64 (hi in the top half)."""
    return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) & _LO32)


def _take_rows(cols, perm):
    """Gather the row columns `cols` (each [N], int32 or int64) through
    `perm` as ONE gather of an [N, W] int32 word matrix; returns the
    columns in their own dtypes, bit for bit.

    The TPU has no 64-bit vector unit: it lowers an int64 gather as two
    u32 gathers, one per half-word, and each pays the full per-index cost
    (v5e, 720,896 rows: 4.3 ns per index for each u32 half of an int64
    [N, 4] gather, 3.3 ns per index for one int32 [N, 8] gather). So an
    int64 column rides as its two words and an int32 column as itself,
    and one index pass moves the whole row: W is 2 per int64 column plus
    1 per int32 column. The split
    is a bitcast: splitting with shifts and masks made the compiler fuse
    the commit phase's audit fold into 32 kernels instead of 5 (v5e: +0.8
    ms per window at 16,384 hosts).

    The sorts below carry only their keys and a row index: TPU compile
    time grows with a sort's operand count (described v5e, PR 21: ~50 s
    per int64 operand at 165k rows; the 11-operand window sorts took the
    udp_flood_10k program to ~19 min), so every payload column follows
    the sort's permutation here instead of riding it as an operand."""
    words = [jax.lax.bitcast_convert_type(c, jnp.int32)
             if c.dtype == jnp.int64 else c[:, None] for c in cols]
    rows = jnp.take(jnp.concatenate(words, axis=1), perm, axis=0,
                    mode="clip")
    out, i = [], 0
    for c, w in zip(cols, words):
        n = w.shape[1]
        r = rows[:, i:i + n]
        out.append(jax.lax.bitcast_convert_type(r, c.dtype) if n == 2
                   else r[:, 0])
        i += n
    return out


class IslandSpec(NamedTuple):
    """Per-shard ("island") execution of the window kernel.

    The reference's parallel design is per-worker locality: each worker owns
    a set of hosts and their event queues, and cross-host pushes go straight
    into the owner's queue (scheduler.c:329-353, worker.c:517-576). The TPU
    equivalent built here: the host axis is split into `num_shards`
    contiguous blocks, each owning a LOCAL event pool and a LOCAL dense
    window (so per-shard sort volume drops num_shards×); cross-shard
    emissions ride a bounded all_to_all exchange at the merge; the round
    barrier is a pmin over the shard axis. Runs identically under
    jax.vmap(axis_name=...) (virtual shards on one chip — batched local
    sorts) and jax.shard_map (real devices).
    """

    axis: str  # mesh/vmap axis name
    num_shards: int  # S
    exchange_slots: int  # X rows per destination shard per window
    # route by params.slot_of table instead of dst//H arithmetic —
    # required once the rebalancer may permute host→shard assignment
    # (compiled in from the start so a rebalance never recompiles)
    use_slot_table: bool = False
    # compile the speculation-violation checks for optimistic windows:
    # LOCAL-dst emissions check against the shard's own done_t progress
    # clocks at the merge (exactly the global engine's check), and
    # FOREIGN emissions are checked at ARRIVAL on the destination shard —
    # after the all_to_all, against the receiver's done_t — so no
    # per-emission collective is ever needed (the exchange the rows
    # already ride IS the collective). The per-shard xmit_min signals
    # combine with one pmin in the attempt loop (parallel/islands.py).
    optimistic: bool = False


def _island_route(
    m_t, m_d, m_s, m_q, m_k, m_p, *,
    win_start, H, C, spec: IslandSpec, slot_of=None,
):
    """Merge-stage routing for the islands engine: one grouping sort sends
    each row toward (destination shard 0..S-1 | local pool), a bounded
    [S, X] block per operand rides ONE all_to_all, and the local pool is
    assembled by concatenation — no third sort (the pool is an unordered
    bag; extraction re-sorts by the full key every window, and truncation
    overflow is handled by the caller's drop/spill accounting).

    Reference analog: scheduler_push into the destination host's locked
    queue (scheduler.c:232-255) — here the destination SHARD's pool, with
    the lock replaced by the collective.

    Rows that miss the bounded exchange (more than X rows for one
    destination shard) stay in the local pool and retry next window; their
    min time is returned so the driver can clamp the next window's END
    below it (the destination must not process past an in-transit event).

    Returns (pool_cols, dropped, sent, deferred, deferred_min) where
    pool_cols = (t, d, s, q, k, plist) each [C].
    """
    S, X = spec.num_shards, spec.exchange_slots
    SX = S * X
    if C <= SX:
        raise ValueError(
            f"per-shard pool capacity {C} must exceed exchange block "
            f"{SX} (= num_shards x exchange_slots)"
        )
    my_shard = jax.lax.axis_index(spec.axis).astype(jnp.int64)
    real = m_t != NEVER
    if slot_of is not None:
        slot = slot_of[jnp.clip(m_d, 0, slot_of.shape[0] - 1)]
    else:
        slot = m_d
    dshard = jnp.clip(slot // H, 0, S - 1).astype(jnp.int64)
    foreign = real & (dshard != my_shard)
    group = jnp.where(foreign, dshard, jnp.int64(S))
    dt = jnp.clip(m_t - win_start, 0, _DT_MAX)
    # Packed (group, dt) key. dt saturates at 2^44 ns (~4.9 h) past the
    # window start: rows beyond that tie and fall back to stable input
    # order — deterministic, and lossless once overflow spills instead of
    # drops; sub-horizon rows (every realistic sim span) order exactly.
    k1_r = (group << _DT_BITS) | dt
    gf = jnp.repeat(jnp.arange(S, dtype=jnp.int64), X)
    k1_f = (gf << _DT_BITS) | _DT_MAX
    z32 = jnp.zeros((SX,), jnp.int32)
    z64 = jnp.zeros((SX,), jnp.int64)
    n_r = k1_r.shape[0]
    cat_cols = [
        jnp.concatenate([m_t, jnp.full((SX,), NEVER, jnp.int64)]),
        jnp.concatenate([m_d, z32]),
        jnp.concatenate([m_s, z32]),
        jnp.concatenate([m_q, z32]),
        jnp.concatenate([m_k, z32]),
    ] + [jnp.concatenate([p, z64]) for p in m_p]
    N = n_r + SX
    iota = jnp.arange(N, dtype=jnp.int32)
    s_k1, perm1 = jax.lax.sort(
        [jnp.concatenate([k1_r, k1_f]), iota], num_keys=2
    )
    s_group = s_k1 >> _DT_BITS
    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), s_group[1:] != s_group[:-1]]
    )
    run_start = jax.lax.cummax(jnp.where(boundary, iota, -1))
    rank = iota - run_start
    # X fillers per group guarantee every exchange slot is claimed (filler
    # rows carry time NEVER; receivers mask them) — the [S, X] block is a
    # plain reshape after the slot sort, exactly the dense-window trick.
    extract = (s_group < S) & (rank < X)
    slot = jnp.where(extract, (s_group * X + rank.astype(jnp.int64)), SX)
    k2 = (slot << _DT_BITS) | (s_k1 & _DT_MAX)
    # ties in k2 keep the first sort's order (iota), as a stable sort would
    _, _, perm = jax.lax.sort([k2, iota, perm1], num_keys=2)
    cols = _take_rows(cat_cols, perm)  # t, d, s, q, k, packed payload
    # filler rows (cat index >= n_r) carry time NEVER: not sent
    sent = jnp.sum(extract & (perm1 < n_r), dtype=jnp.int64)

    recv_cols = []
    for c in cols:
        blk = c[:SX].reshape((S, X) + c.shape[1:])
        r = jax.lax.all_to_all(blk, spec.axis, 0, 0)
        recv_cols.append(r.reshape((SX,) + c.shape[1:]))

    C_keep = C - SX
    rem = [c[SX:] for c in cols]
    rem_t, rem_d = rem[0], rem[1]
    dropped = jnp.sum(rem_t[C_keep:] != NEVER, dtype=jnp.int64)
    rd = rem_d[:C_keep]
    if slot_of is not None:
        rslot = slot_of[jnp.clip(rd, 0, slot_of.shape[0] - 1)]
    else:
        rslot = rd
    def_mask = (rem_t[:C_keep] != NEVER) & (
        jnp.clip(rslot // H, 0, S - 1).astype(jnp.int64) != my_shard
    )
    deferred = jnp.sum(def_mask, dtype=jnp.int64)
    deferred_min = jnp.min(
        jnp.where(def_mask, rem_t[:C_keep], NEVER)
    )
    pool_cols = [
        jnp.concatenate([r[:C_keep], rc])
        for r, rc in zip(rem, recv_cols)
    ]
    return pool_cols, dropped, sent, deferred, deferred_min


def _dense_extract(pool: EventPool, win_start, win_end, H: int, Kc: int,
                   PP: int, lrow=None):
    """Extract the window into a dense [H, Kc] matrix with two key sorts,
    a scan and ONE whole-row gather. The sorts carry only keys and a row
    index; the event columns and payload words follow the final
    permutation in the gather (_take_rows: an operand per column made the
    TPU compile of these sorts take tens of minutes).

    Sort cost on TPU scales with rows × comparator stages (measured:
    payload-operand packing barely moved it, key count does), so the
    4-component key (dst | H-sentinel, time, src, seq) is PACKED into two
    i64 keys:

        k1 = run_key << 44 | clip(time - win_start, 0, 2^44-1)
        k2 = src << 32 | seq (zero-extended)

    Exact for every in-window row: run_key < H only for in-window rows,
    whose time ∈ [win_start, win_end) with win_end - win_start ≤ runahead
    ≪ 2^44; out-of-window rows (run_key = H) may clip dt, but their order
    is irrelevant — the next merge re-sorts everything by time. Filler
    rows (Kc per host, dt = 2^44-1 > any real in-window dt) sort after
    every real row of their host.

    A cummax scan derives each row's rank within its host run (no
    searchsorted — its method="sort" lowers to a scatter). Sort 2 by dense
    slot id (h*Kc + rank) lands extracted rows so the window matrix is a
    plain reshape; everything else becomes the merge leftovers.

    Replaces per-host priority queues (scheduler_policy_host_single.c:
    18-54) and their locks with two sorts shared by all hosts."""
    C = pool.capacity
    HK = H * Kc
    N = C + HK
    hosts = jnp.arange(H, dtype=jnp.int32)
    # Local row of each event's destination: dst itself on the global
    # engine; the caller passes the shard-relative row under islands
    # (contiguous-block arithmetic or the slot_of rebalance table).
    # Foreign rows (in-transit exchange deferrals) fall outside [0, H) and
    # must not extract — they ride the tail into the merge, where
    # _island_route retries them.
    if lrow is None:
        lrow = pool.dst
    local = (lrow >= 0) & (lrow < H)
    inwin = (pool.time < win_end) & local
    run_key = jnp.where(inwin, lrow, jnp.int32(H)).astype(jnp.int64)
    dt = jnp.clip(pool.time - win_start, 0, _DT_MAX)
    k1_r = (run_key << _DT_BITS) | dt
    k2_r = (pool.src.astype(jnp.int64) << 32) | (
        pool.seq.astype(jnp.int64) & 0xFFFFFFFF
    )
    key_f = jnp.repeat(hosts, Kc)  # [HK] filler host ids
    k1_f = (key_f.astype(jnp.int64) << _DT_BITS) | _DT_MAX
    cat_k1 = jnp.concatenate([k1_r, k1_f])
    cat_k2 = jnp.concatenate([k2_r, jnp.zeros((HK,), jnp.int64)])
    zf32 = jnp.zeros((HK,), jnp.int32)
    zf64 = jnp.zeros((HK,), jnp.int64)
    cat_cols = [
        jnp.concatenate([pool.time, jnp.full((HK,), NEVER, jnp.int64)]),
        # TRUE dst rides along (fillers carry their host id)
        jnp.concatenate([pool.dst, key_f]),
        jnp.concatenate([pool.src, zf32]),
        jnp.concatenate([pool.seq, zf32]),
        jnp.concatenate([pool.kind, zf32]),
    ] + [jnp.concatenate([pool.payload[:, w], zf64]) for w in range(PP)]
    iota = jnp.arange(N, dtype=jnp.int32)
    # the row index as the last key orders ties as a stable sort would
    s_k1, _, perm1 = jax.lax.sort([cat_k1, cat_k2, iota], num_keys=3)
    s_key = (s_k1 >> _DT_BITS).astype(jnp.int32)
    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), s_key[1:] != s_key[:-1]]
    )
    run_start = jax.lax.cummax(jnp.where(boundary, iota, -1))
    rank = iota - run_start
    extract = (s_key < H) & (rank < Kc)
    slot = jnp.where(extract, s_key * Kc + rank, jnp.int32(N))
    # slot, then the first sort's order: one packed key (both < 2^31)
    _, perm = jax.lax.sort([_pack2(slot, iota), perm1], num_keys=1)
    o_t, o_d, o_s, o_q, o_k, *o_p = _take_rows(cat_cols, perm)
    d_t = o_t[:HK].reshape(H, Kc)
    d_s = o_s[:HK].reshape(H, Kc)
    d_q = o_q[:HK].reshape(H, Kc)
    d_k = o_k[:HK].reshape(H, Kc)
    d_p = jnp.stack([o[:HK].reshape(H, Kc) for o in o_p], axis=-1)
    dense = _DenseWindow(time=d_t, src=d_s, seq=d_q, kind=d_k, payload=d_p)
    tail = _Tail(
        time=o_t[HK:], src=o_s[HK:], seq=o_q[HK:],
        kind=o_k[HK:], dst=o_d[HK:],
        payload=[o[HK:] for o in o_p],
    )
    return dense, tail


def _read_col(dense: _DenseWindow, col, Kc: int):
    """Read event fields at per-host column `col` via one-hot masked
    reduces (soa.get_at) — NOT take_along_axis, whose gather serializes per
    element on TPU; the [H, Kc] compare+select runs at full vector
    bandwidth (XLA CSE merges the repeated hit masks). `col` must lie in
    [0, Kc). Returns (time, src, seq, kind, payload)."""
    return (
        soa.get_at(dense.time, col),
        soa.get_at(dense.src, col),
        soa.get_at(dense.seq, col),
        soa.get_at(dense.kind, col),
        soa.get_at(dense.payload, col),
    )


def _inbox_min(inbox: _Inbox):
    """Per-host lexicographic min of the inbox by (time, src, seq).
    Returns (time, src, seq, slot) each [H].

    Tournament reduction (log2 B rounds of elementwise compares) instead of
    a lax.sort: B is tiny and TPU's bitonic sort costs ~ms at H=8k where
    this costs microseconds."""
    t, s, q = inbox.time, inbox.src, inbox.seq
    B = t.shape[1]
    slot = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32), t.shape)
    while B > 1:
        half = (B + 1) // 2
        t1, s1, q1, i1 = t[:, :half], s[:, :half], q[:, :half], slot[:, :half]
        t2 = t[:, half:]
        pad = half - t2.shape[1]
        if pad:
            t2 = jnp.pad(t2, ((0, 0), (0, pad)), constant_values=NEVER)
            s2 = jnp.pad(s[:, half:], ((0, 0), (0, pad)))
            q2 = jnp.pad(q[:, half:], ((0, 0), (0, pad)))
            i2 = jnp.pad(slot[:, half:], ((0, 0), (0, pad)))
        else:
            s2, q2, i2 = s[:, half:], q[:, half:], slot[:, half:]
        take2 = _key_lt(t2, s2, q2, t1, s1, q1)
        t = jnp.where(take2, t2, t1)
        s = jnp.where(take2, s2, s1)
        q = jnp.where(take2, q2, q1)
        slot = jnp.where(take2, i2, i1)
        B = half
    return t[:, 0], s[:, 0], q[:, 0], slot[:, 0]


def _key_lt(t1, s1, q1, t2, s2, q2):
    """(t1,s1,q1) < (t2,s2,q2) lexicographically (same dst implied)."""
    return (t1 < t2) | ((t1 == t2) & ((s1 < s2) | ((s1 == s2) & (q1 < q2))))


def _set_col(arr, col, mask, val):
    """arr[h, col[h]] = val[h] for masked hosts, as a pure elementwise
    select over [H, B(, P)] — avoids XLA scatter, which serializes on TPU.
    `val` may be scalar, [H], or [H, P] matching arr's trailing dims."""
    B = arr.shape[1]
    cols = jnp.arange(B, dtype=jnp.int32)
    hit = mask[:, None] & (cols[None, :] == col[:, None])  # [H, B]
    val = jnp.asarray(val, arr.dtype)
    if arr.ndim == 3:
        if val.ndim == 2:
            val = val[:, None, :]
        return jnp.where(hit[:, :, None], val, arr)
    if val.ndim == 1:
        val = val[:, None]
    return jnp.where(hit, val, arr)


# ---------------------------------------------------------------------------
# The window step factory
# ---------------------------------------------------------------------------


def make_window_step(
    handlers: dict[int, Handler],
    num_hosts: int,
    K: int = 32,
    B: int = 8,
    O: int = 64,
    max_iters: int | None = None,
    bulk_kinds: dict[int, int] | None = None,
    matrix_handlers: dict[int, Callable] | None = None,
    with_cpu_model: bool = False,
    bulk_gate: Callable | None = None,
    bulk_self_excluded: bool = False,
    payload_words: int = PAYLOAD_WORDS,
    island: IslandSpec | None = None,
    audit: bool = True,
    _force_path: str | None = None,  # "matrix"|"loop": testing/profiling only
):
    """Build step(state, params, win_start, win_end) -> (state, min_next).

    ``handlers`` maps event kind → handler; handler order within a micro-step
    follows ascending kind (fixed, deterministic).

    ``bulk_kinds`` maps kind → G: a host whose candidate event has that kind
    may consume up to G CONSECUTIVE same-kind run events in one iteration
    (the handler is invoked once per taken column, in key order), dividing
    the iteration count for kinds that dominate a host's window. SAFETY
    CONTRACT: a bulk kind's handler must never emit a SELF event with
    time < win_end — such an emission could carry a key between two bulked
    events and would deserve to interleave, which the batch forecloses.
    (Cross-host emissions always land >= win_end under conservative
    windows; PHOLD's message kind satisfies this by construction.)
    At most one bulk kind is supported currently.

    ``bulk_gate(state, params, win_start, win_end) -> [H] i32`` makes the
    contract DYNAMIC for kinds that are only conditionally bulk-safe (the
    net stack's packet arrivals): it returns, per host, how many EXTRA
    same-kind events may be batched this micro-step — 0 disables batching
    for hosts whose handler might emit a sub-window self event (queued
    router, exhausted tokens, armed pumps). ``bulk_self_excluded`` further
    restricts batches to events whose src differs from the host (loopback
    arrivals reply to self at the same timestamp).
    """
    H = num_hosts
    if max_iters is None:
        max_iters = K + 4 * B + 16
    kinds = sorted(handlers)
    if bulk_kinds and len(bulk_kinds) > 1:
        raise ValueError("at most one bulk kind is supported")
    bulk_kind, G = (
        next(iter(bulk_kinds.items())) if bulk_kinds else (None, 1)
    )
    if bulk_kind is not None and bulk_kind not in handlers:
        raise ValueError(f"bulk kind {bulk_kind} has no handler")
    matrix_handlers = matrix_handlers or {}

    def step(state: SimState, params: NetParams, win_start, win_end):
        P = payload_words  # logical payload words (per-sim sized)
        PP = soa.packed_words(P)  # packed i64 columns actually carried
        win_start = jnp.asarray(win_start, jnp.int64)
        win_end = jnp.asarray(win_end, jnp.int64)
        state = state.replace(now=win_start)
        # GLOBAL host id per local row: arange on the global engine, the
        # shard's contiguous block (or rebalanced permutation) under
        # islands. Every "my host id" use below (self-routing, emission
        # src stamping) is gid, never arange.
        gid = state.host.gid
        def _box_lrow(bd):
            """dst → shard-local row for any dst column (pool, box, or
            exchange-received rows); foreign dsts land outside [0, H)."""
            if island.use_slot_table:
                b = jax.lax.axis_index(island.axis).astype(jnp.int32) * H
                return params.slot_of[
                    jnp.clip(bd, 0, params.slot_of.shape[0] - 1)
                ] - b
            return bd - gid[0]

        with jax.named_scope("win.extract"):
            _lrow = None if island is None else _box_lrow(state.pool.dst)

        def _obs_win_bump(state, *slots):
            """One fused add to the telemetry block's window-plane row.
            Under islands the bump is scaled by (axis_index == 0) so the
            summed-at-fetch counts equal the global engine's. Compiled out
            entirely when the block is disabled."""
            if state.obs is None:
                return state
            vec = obs_mod.win_bump_vec(*slots)
            if island is not None:
                vec = vec * (
                    jax.lax.axis_index(island.axis) == 0
                ).astype(jnp.int64)
            return state.replace(
                obs=state.obs.replace(win=state.obs.win + vec)
            )

        # Static per-kind emission bound: probe the handlers once at trace
        # time with an all-masked-off event and count emit() calls per
        # kind. A host processes exactly ONE event (of one kind) per
        # iteration, so its worst-case outbox demand is the emit-call count
        # of THAT kind's handler. The backpressure below stalls a host
        # whose outbox can't absorb that demand — nothing is ever dropped.
        # The probe's state/ops are discarded (XLA dead-code-eliminates).
        probe = Emitter()
        pv = EventView(
            mask=jnp.zeros((H,), jnp.bool_),
            time=jnp.zeros((H,), jnp.int64),
            src=jnp.zeros((H,), jnp.int32),
            seq=jnp.zeros((H,), jnp.int32),
            kind=jnp.zeros((H,), jnp.int32),
            payload=jnp.zeros((H, P), jnp.int32),
        )
        E_by_kind = np.zeros(max(kinds) + 1 if kinds else 1, dtype=np.int32)
        pstate = state
        for k in kinds:
            before = len(probe.records)
            pstate = handlers[k](pstate, pv, probe, params)
            E_by_kind[k] = len(probe.records) - before
        del pstate
        if int(E_by_kind.max()) > O:
            worst = int(E_by_kind.argmax())
            raise ValueError(
                f"outbox_slots O={O} cannot absorb kind {worst}'s worst-"
                f"case emissions E={int(E_by_kind.max())}; raise "
                f"experimental.outbox_slots"
            )
        G_run = G
        if bulk_kind is not None and int(E_by_kind[bulk_kind]) * G > O:
            if bulk_gate is None:
                raise ValueError(
                    f"outbox_slots O={O} cannot absorb a full bulk batch "
                    f"(kind {bulk_kind}: {int(E_by_kind[bulk_kind])} "
                    f"emissions x G={G}); raise outbox_slots or lower the "
                    f"bulk width"
                )
            # gated batching degrades gracefully: clamp the batch width so
            # a full batch always fits the outbox (the gate already makes
            # batching best-effort per host)
            G_run = max(1, O // max(1, int(E_by_kind[bulk_kind])))

        def assemble(state, m_t, m_d, m_s, m_q, m_k, m_p):
            """Merge candidates → next pool. Global engine: ONE 1-key stable
            sort by time, truncate to capacity. Islands: route through
            _island_route (grouping sort + bounded all_to_all + concat
            assembly) — cross-shard rows land in their owner's pool here,
            the TPU form of scheduler_push (scheduler.c:232-255).

            Returns (state, arrival_viol_min): the second value is the
            optimistic-islands ARRIVAL check — the earliest exchange-
            received row that lands at/behind its destination host's
            done_t progress clock (NEVER otherwise, and always NEVER for
            the global engine, where emissions are checked before the
            merge instead)."""
            C = state.pool.capacity
            arrival_min = jnp.asarray(NEVER, jnp.int64)
            if island is None:
                iota = jnp.arange(m_t.shape[0], dtype=jnp.int32)
                s_t, perm = jax.lax.sort([m_t, iota], num_keys=2)
                dropped = jnp.sum(s_t[C:] != NEVER, dtype=jnp.int64)
                perm = perm[:C]
                g_d, g_s, g_q, g_k, *g_p = _take_rows(
                    [m_d, m_s, m_q, m_k] + list(m_p), perm
                )
                new_pool = EventPool(
                    time=s_t[:C], dst=g_d, src=g_s, seq=g_q, kind=g_k,
                    payload=jnp.stack(g_p, axis=-1),
                )
                return state.replace(
                    pool=new_pool,
                    exch_deferred_min=jnp.asarray(NEVER, jnp.int64),
                    counters=state.counters.replace(
                        pool_overflow_dropped=(
                            state.counters.pool_overflow_dropped + dropped
                        )
                    ),
                ), arrival_min
            cols, dropped, sent, deferred, dmin = _island_route(
                m_t, m_d, m_s, m_q, m_k, m_p,
                win_start=win_start, H=H, C=C, spec=island,
                slot_of=params.slot_of if island.use_slot_table else None,
            )
            if island.optimistic:
                # Arrival check: rows just received through the exchange
                # occupy the pool tail block [C_keep:). One received row
                # behind its destination's progress clock means this
                # shard speculated past an in-flight delivery — surface
                # its time so the attempt loop rolls the window back.
                # Covers rows that DEFERRED in earlier sub-steps too:
                # they re-arrive here, and done_t only grows within an
                # attempt, so a missed ordering is still caught.
                C_keep = C - island.num_shards * island.exchange_slots
                recv_t, recv_d = cols[0][C_keep:], cols[1][C_keep:]
                lr = _box_lrow(recv_d)
                dst_last = state.host.done_t[jnp.clip(lr, 0, H - 1)]
                vio = (recv_t != NEVER) & (recv_t <= dst_last)
                arrival_min = jnp.min(jnp.where(vio, recv_t, NEVER))
            new_pool = EventPool(
                time=cols[0], dst=cols[1], src=cols[2],
                seq=cols[3], kind=cols[4],
                payload=jnp.stack(cols[5:], axis=-1),
            )
            c = state.counters
            return state.replace(
                pool=new_pool,
                exch_deferred_min=dmin,
                counters=c.replace(
                    pool_overflow_dropped=c.pool_overflow_dropped + dropped,
                    exchange_sent=c.exchange_sent + sent,
                    exchange_deferred=c.exchange_deferred + deferred,
                ),
            ), arrival_min

        # Merge-absorption budget for the pool-headroom stall: the merge
        # truncates at capacity (minus the islands' reserved exchange
        # block), so a window may generate at most C_keep − occupancy new
        # box rows without dropping. Computed once per window (the pool
        # does not change until the merge).
        _C_keep = state.pool.capacity - (
            island.num_shards * island.exchange_slots if island else 0
        )
        pool_budget = jnp.int32(_C_keep) - jnp.sum(
            state.pool.time != NEVER, dtype=jnp.int32
        )

        # The loop path's machinery closes over the dense window extraction;
        # building it in a factory keeps the extraction sorts INSIDE the
        # run_loop cond branch, so the matrix fast path never pays for them.
        # Kc = K + 1 columns: column K is never consumed (the cursor gate is
        # ptr < K) — it exists purely to expose the earliest DEFERRED
        # event's full key per host. A self-emission whose key (time,
        # emitting host, seq) is >= that deferred key must bypass the inbox
        # and go to the pool, otherwise it could be processed ahead of the
        # deferred leftover; the full-key compare keeps that routing exact
        # under nanosecond ties.
        def make_loop_fns(dense: _DenseWindow, tail: _Tail):
            Kc = K + 1
            defer_time = dense.time[:, K]
            defer_src = dense.src[:, K]
            defer_seq = dense.seq[:, K]
            carry0 = (
                jnp.zeros((H,), dtype=jnp.int32),  # ptr (consumed per host)
                _Inbox.empty(H, B, PP),
                _Outbox.empty(H, O, PP),
                jnp.int32(0),  # iteration counter
                jnp.bool_(True),  # work remaining
            )

            def cond(carry):
                _, _, _, _, it, work = carry
                return work & (it < max_iters)

            def body(carry):
                state, ptr, inbox, outbox, it, _ = carry

                # --- candidate per host: dense-matrix head vs inbox min ---
                # (one-hot reads; ptr <= K < Kc always in range)
                m_t_raw, m_src, m_seq, m_kind, m_payload = _read_col(
                    dense, ptr, Kc
                )
                in_run = (ptr < K) & (m_t_raw != NEVER)
                m_time = jnp.where(in_run, m_t_raw, NEVER)
                i_time, i_src, i_seq, i_slot = _inbox_min(inbox)
                use_inbox = _key_lt(i_time, i_src, i_seq, m_time, m_src, m_seq)
                ev_time = jnp.where(use_inbox, i_time, m_time)

                i_kind = soa.get_at(inbox.kind, i_slot)
                ev_kind = jnp.where(use_inbox, i_kind, m_kind)

                # --- bulk batch planning (before the room check, which must
                # cover the whole batch's emissions): extend the run take with
                # up to G-1 further CONSECUTIVE events of the bulk kind, each
                # required to precede the inbox head in key order so nothing
                # that deserves to interleave is foreclosed. ---
                bulk_t, bulk_s, bulk_q, bulk_p, bulk_m = [], [], [], [], []
                if bulk_kind is not None and G_run > 1:
                    prev = (
                        (ev_time < win_end) & ~use_inbox & (ev_kind == bulk_kind)
                    )
                    if bulk_self_excluded:
                        # the HEAD is part of the batch too: a loopback
                        # head may emit a same-time self reply that
                        # deserves to interleave before any batched extra
                        prev = prev & (m_src != gid)
                    if bulk_gate is not None:
                        gate = bulk_gate(state, params, win_start, win_end)
                        prev = prev & (gate > 0)
                    for g in range(1, G_run):
                        ing = ptr + g < K
                        tg_r, sg, qg, kg, pg = _read_col(
                            dense, jnp.where(ing, ptr + g, 0), Kc
                        )
                        ing = ing & (tg_r != NEVER)
                        tg = jnp.where(ing, tg_r, NEVER)
                        okg = (
                            prev & ing & (kg == bulk_kind) & (tg < win_end)
                            & _key_lt(tg, sg, qg, i_time, i_src, i_seq)
                        )
                        if bulk_self_excluded:
                            okg = okg & (sg != gid)
                        if bulk_gate is not None:
                            okg = okg & (gate >= g)
                        bulk_t.append(tg)
                        bulk_s.append(sg)
                        bulk_q.append(qg)
                        bulk_p.append(pg)
                        bulk_m.append(okg)
                        prev = okg
                    g_extra = jnp.sum(
                        jnp.stack(bulk_m, axis=1), axis=1, dtype=jnp.int32
                    )
                else:
                    g_extra = jnp.zeros((H,), dtype=jnp.int32)

                # Outbox backpressure: a host whose outbox cannot absorb this
                # event-kind's worst-case emissions (times the batch width)
                # stalls — its events stay queued and defer to the next window
                # via the merge (never dropped). Per-kind worst cases are
                # static python ints, so the lookup is an unrolled select —
                # not an [H]-gather.
                need_base = jnp.zeros((H,), dtype=jnp.int32)
                for k in kinds:
                    e_k = int(E_by_kind[k])
                    if e_k:
                        need_base = jnp.where(ev_kind == k, e_k, need_base)
                need = need_base * (1 + g_extra)
                room = (outbox.count + need) <= O
                # Pool-headroom backpressure (the never-drop invariant,
                # scheduler.c:232-255): the merge can only absorb
                # C − occupancy new box rows, so hosts whose emissions
                # would overflow the pool STALL this window (defer, never
                # drop). Budget is claimed in host-index order via an
                # exclusive cumsum — deterministic. NOT a progress
                # guarantee: box rows accumulated by earlier micro
                # -iterations (box_used) already count against the budget,
                # so with occupancy deep in the red zone even host 0 can
                # fail the gate and the window commits nothing; the driver
                # surfaces that as the headroom-stall RuntimeError in the
                # run loops (the spill tier then needs a larger pool to
                # place even one window's inflow). Common case (ample
                # headroom): every host passes, the gate folds away.
                hot = ev_time < win_end
                box_used = (
                    jnp.sum(outbox.count)
                    + jnp.sum(inbox.time != NEVER, dtype=jnp.int32)
                )
                need_hot = jnp.where(hot, need, 0)
                cum = jnp.cumsum(need_hot) - need_hot  # exclusive
                fits = (box_used + cum + need_hot) <= pool_budget
                valid = hot & room & fits
                stalled = hot & ~(room & fits)

                # --- CPU model (host/cpu.c analog): a loaded host's events
                # serialize on its virtual CPU — event at t EXECUTES at
                # max(t, cpu_avail), advancing cpu_avail by cpu_cost.
                # Selection/ordering stay keyed on the ORIGINAL times; only
                # execution (and thus emission) timestamps shift. Compiled
                # out entirely when the model is off.
                if with_cpu_model:
                    cost = state.host.cpu_cost
                    exec_t = jnp.maximum(ev_time, state.host.cpu_avail)
                    bulk_exec = []
                    prev_e = exec_t
                    for bt in bulk_t:
                        e = jnp.maximum(bt, prev_e + cost)
                        bulk_exec.append(e)
                        prev_e = e
                else:
                    exec_t = ev_time
                    bulk_exec = bulk_t

                i_payload = soa.get_at(inbox.payload, i_slot)
                ev = EventView(
                    mask=valid,
                    time=exec_t,
                    src=jnp.where(use_inbox, i_src, m_src),
                    seq=jnp.where(use_inbox, i_seq, m_seq),
                    kind=ev_kind,
                    # handlers see the unpacked i32 view (payloads travel
                    # packed through sorts/boxes — soa.pack_words)
                    payload=soa.unpack_words(
                        jnp.where(use_inbox[:, None], i_payload, m_payload),
                        P,
                    ),
                )

                # --- consume the chosen event(s) ---
                bulk_valid = [bm & valid for bm in bulk_m]  # [H] per extra col
                taken_extra = (
                    jnp.sum(jnp.stack(bulk_valid, axis=1), axis=1,
                            dtype=jnp.int32)
                    if bulk_valid else jnp.zeros((H,), dtype=jnp.int32)
                )
                last_t = exec_t
                for bt, bv in zip(bulk_exec, bulk_valid):
                    last_t = jnp.where(bv, bt, last_t)
                with jax.named_scope("win.commit"):
                    done_t = jnp.where(valid, last_t, state.host.done_t)
                    state = state.replace(
                        host=state.host.replace(done_t=done_t)
                    )
                if with_cpu_model:
                    delay = jnp.where(valid, exec_t - ev_time, 0)
                    for bt, be, bv in zip(bulk_t, bulk_exec, bulk_valid):
                        delay = delay + jnp.where(bv, be - bt, 0)
                    state = state.replace(
                        host=state.host.replace(
                            cpu_avail=jnp.where(
                                valid, last_t + cost, state.host.cpu_avail
                            )
                        ),
                        counters=state.counters.replace(
                            cpu_delay_applied=state.counters.cpu_delay_applied
                            + jnp.sum(delay, dtype=jnp.int64)
                        ),
                    )
                ptr = jnp.where(valid & ~use_inbox, ptr + 1 + taken_extra, ptr)
                inbox = inbox.replace(
                    time=_set_col(inbox.time, i_slot, valid & use_inbox, NEVER)
                )

                # --- run handlers (ascending kind; masked SoA updates); the
                # bulk kind's handler runs once per taken column, in key order
                emitter = Emitter()
                for k in kinds:
                    hev = ev.replace(mask=valid & (ev.kind == k))
                    state = handlers[k](state, hev, emitter, params)
                    if k == bulk_kind:
                        for g in range(len(bulk_valid)):
                            gev = EventView(
                                mask=bulk_valid[g],
                                time=bulk_exec[g],
                                src=bulk_s[g],
                                seq=bulk_q[g],
                                kind=jnp.full((H,), k, dtype=jnp.int32),
                                payload=soa.unpack_words(bulk_p[g], P),
                            )
                            state = handlers[k](state, gev, emitter, params)

                with jax.named_scope("win.commit"):
                    c = state.counters
                    state = state.replace(
                        counters=c.replace(
                            events_committed=c.events_committed
                            + jnp.sum(valid, dtype=jnp.int64)
                            + jnp.sum(taken_extra, dtype=jnp.int64),
                            outbox_stall_deferred=c.outbox_stall_deferred
                            + jnp.sum(stalled, dtype=jnp.int64),
                            micro_steps=c.micro_steps + 1,
                        )
                    )
                    if state.obs is not None:
                        # telemetry block: per-host committed count + the
                        # virtual-time frontier (events process in key order
                        # per host, so a where-select IS the running max)
                        ob = state.obs
                        hd = ob.host_digest
                        if audit:
                            # determinism-audit chain (obs/audit.py): fold
                            # the head event then each bulk column — per-host
                            # key order, the order every engine layout
                            # commits in. Keys use the ORIGINAL event time
                            # (not the CPU model's exec shift), so chains
                            # are model-stable.
                            hd = audit_mod.fold(
                                hd, valid, ev_time, ev.src, gid, ev_kind
                            )
                            for bt, bs, bv in zip(
                                bulk_t, bulk_s, bulk_valid
                            ):
                                hd = audit_mod.fold(
                                    hd, bv, bt, bs, gid, bulk_kind
                                )
                        state = state.replace(obs=ob.replace(
                            host_events=ob.host_events
                            + valid.astype(jnp.int64)
                            + taken_extra.astype(jnp.int64),
                            host_last_t=jnp.where(
                                valid, last_t, ob.host_last_t
                            ),
                            host_digest=hd,
                        ))
                    if state.flight is not None:
                        # flight recorder (obs/flight.py): append the
                        # committed records at each host's ring cursor, head
                        # then bulk columns — the same commit order the
                        # digest folds in
                        fl = flight_mod.record(
                            state.flight, valid, ev_time, ev.src, ev.seq,
                            ev_kind,
                        )
                        for bt, bs, bq, bv in zip(
                            bulk_t, bulk_s, bulk_q, bulk_valid
                        ):
                            fl = flight_mod.record(
                                fl, bv, bt, bs, bq, bulk_kind
                            )
                        state = state.replace(flight=fl)

                # --- route emissions (order fixes per-source seq numbers) ---
                for em in emitter.records:
                    emp = soa.pack_words(em.payload)  # [H, PP]
                    with jax.named_scope("win.commit"):
                        seq = state.host.seq_next
                        state = state.replace(
                            host=state.host.replace(
                                seq_next=jnp.where(em.mask, seq + 1, seq)
                            )
                        )
                    # Self-emissions at or past the host's earliest deferred
                    # leftover (full-key compare: exact under time ties) must
                    # not jump the queue: route them through the pool.
                    is_self = (
                        em.mask
                        & (em.dst == gid)
                        & (em.time < win_end)
                        & _key_lt(em.time, gid, seq,
                                  defer_time, defer_src, defer_seq)
                    )

                    free = inbox.time == NEVER  # [H, B]
                    ff = jnp.argmax(free, axis=1).astype(jnp.int32)
                    has_free = jnp.any(free, axis=1)
                    ins = is_self & has_free
                    # Inbox overflow DEFERS to the pool via the outbox (processed
                    # next window, late but never lost — a lost NIC pump event
                    # would wedge its queue); the counter records the deferral.
                    to_out = em.mask & ~ins
                    inbox = inbox.replace(
                        time=_set_col(inbox.time, ff, ins, em.time),
                        src=_set_col(inbox.src, ff, ins, gid),
                        seq=_set_col(inbox.seq, ff, ins, seq),
                        kind=_set_col(inbox.kind, ff, ins, em.kind),
                        payload=_set_col(inbox.payload, ff, ins, emp),
                    )

                    ocol = outbox.count  # next free outbox column per host
                    put = to_out & (ocol < O)
                    outbox = outbox.replace(
                        time=_set_col(outbox.time, ocol, put, em.time),
                        dst=_set_col(outbox.dst, ocol, put, em.dst),
                        src=_set_col(outbox.src, ocol, put, gid),
                        seq=_set_col(outbox.seq, ocol, put, seq),
                        kind=_set_col(outbox.kind, ocol, put, em.kind),
                        payload=_set_col(outbox.payload, ocol, put, emp),
                        count=outbox.count + put.astype(jnp.int32),
                    )
                    with jax.named_scope("win.commit"):
                        c = state.counters
                        state = state.replace(
                            counters=c.replace(
                                events_emitted=c.events_emitted
                                + jnp.sum(em.mask, dtype=jnp.int64),
                                inbox_overflow_deferred=(
                                    c.inbox_overflow_deferred
                                    + jnp.sum(is_self & ~has_free,
                                              dtype=jnp.int64)
                                ),
                                outbox_overflow_dropped=(
                                    c.outbox_overflow_dropped
                                    + jnp.sum(to_out & ~put, dtype=jnp.int64)
                                ),
                            )
                        )

                work = jnp.any(valid)
                return (state, ptr, inbox, outbox, it + 1, work)

            def finish(state, ptr, bt, bd, bs, bq, bk, bp):
                """Merge: unconsumed dense cells ∪ tail rows ∪ box rows
                (flattened outbox + inbox leftovers) with ONE 1-key stable
                sort by time carrying every event column and payload word as
                operands — no scatters and no payload-indirection gathers
                (both serialize on TPU). A dense cell is consumed iff its
                column is below the host's final cursor — pure elementwise.
                Also derives the speculation-violation signal: a cross-host
                box emission targeting time t violates iff its DESTINATION
                host already processed an event at time >= t since the
                optimistic synchronizer's window began (host.done_t) —
                impossible under conservative windows, so xmit_min stays
                NEVER there."""
                pool = state.pool
                C = pool.capacity
                dcols = jnp.arange(Kc, dtype=jnp.int32)
                left = dcols[None, :] >= ptr[:, None]  # unconsumed cells
                l_t = jnp.where(left, dense.time, NEVER).reshape(-1)
                l_d = jnp.broadcast_to(gid[:, None], (H, Kc)).reshape(-1)
                l_s = dense.src.reshape(-1)
                l_q = dense.seq.reshape(-1)
                l_k = dense.kind.reshape(-1)

                m_t = jnp.concatenate([l_t, tail.time, bt])
                m_d = jnp.concatenate([l_d, tail.dst, bd])
                m_s = jnp.concatenate([l_s, tail.src, bs])
                m_q = jnp.concatenate([l_q, tail.seq, bq])
                m_k = jnp.concatenate([l_k, tail.kind, bk])
                m_p = [
                    jnp.concatenate(
                        [dense.payload[:, :, w].reshape(-1), tail.payload[w],
                         bp[:, w]]
                    )
                    for w in range(PP)
                ]
                with jax.named_scope("win.commit"):
                    if bt.shape[0] and (island is None or island.optimistic):
                        cross = (bd != bs) & (bt != NEVER)
                        done_t = state.host.done_t
                        if island is None:
                            dst_last = done_t[jnp.clip(bd, 0, H - 1)]
                            violates = cross & (bt <= dst_last)
                        else:
                            # islands: only LOCAL-dst emissions can be
                            # checked against this shard's progress clocks;
                            # foreign ones are checked at ARRIVAL on their
                            # owner (assemble's arrival_min) — no per-row
                            # collective
                            lr = _box_lrow(bd)
                            loc = (lr >= 0) & (lr < H)
                            dst_last = done_t[jnp.clip(lr, 0, H - 1)]
                            violates = cross & loc & (bt <= dst_last)
                        xmit_min = jnp.min(jnp.where(violates, bt, NEVER))
                    else:
                        xmit_min = jnp.asarray(NEVER, jnp.int64)
                state, arrival_min = assemble(
                    state, m_t, m_d, m_s, m_q, m_k, m_p
                )
                with jax.named_scope("win.commit"):
                    state = state.replace(
                        xmit_min=jnp.minimum(xmit_min, arrival_min)
                    )
                return state, jnp.min(state.pool.time)

            return carry0, cond, body, finish

        def run_loop(state):
            with jax.named_scope("win.commit"):
                state = _obs_win_bump(
                    state, obs_mod.WIN_WINDOWS, obs_mod.WIN_LOOP
                )
            with jax.named_scope("win.extract"):
                dense, tail = _dense_extract(
                    state.pool, win_start, win_end, H, K + 1, PP, lrow=_lrow,
                )
            with jax.named_scope("win.handlers"):
                carry0, cond, body, finish = make_loop_fns(dense, tail)
                state, ptr, inbox, outbox, _, _ = jax.lax.while_loop(
                    cond, body, (state,) + carry0
                )
            with jax.named_scope("win.merge"):
                hostsB = jnp.broadcast_to(
                    gid[:, None], inbox.time.shape
                ).reshape(-1)
                return finish(
                    state, ptr,
                    jnp.concatenate(
                        [outbox.time.reshape(-1), inbox.time.reshape(-1)]
                    ),
                    jnp.concatenate([outbox.dst.reshape(-1), hostsB]),
                    jnp.concatenate(
                        [outbox.src.reshape(-1), inbox.src.reshape(-1)]
                    ),
                    jnp.concatenate(
                        [outbox.seq.reshape(-1), inbox.seq.reshape(-1)]
                    ),
                    jnp.concatenate(
                        [outbox.kind.reshape(-1), inbox.kind.reshape(-1)]
                    ),
                    jnp.concatenate(
                        [outbox.payload.reshape(-1, PP),
                         inbox.payload.reshape(-1, PP)]
                    ),
                )

        def run_matrix(state):
            """Whole-window vectorized path: when EVERY in-window event has
            the bulk kind, there is no intra-window feedback (the bulk
            safety contract forbids self-emissions below win_end), so the
            full [H, K] window matrix is processed in ONE handler pass —
            no micro-step loop at all. PHOLD-class models hit this every
            window; it is the PDES "superstep" optimization.

            TPU note (profiled on v5e): large GATHERS serialize per
            index (3-4 ns each; a 64-bit gather makes one pass per 32-bit
            half, so _take_rows moves 32-bit words), while sorts and scans
            run at memory bandwidth, so this path is built from sorts,
            cumulative scans, reshapes and one row gather per permutation
            (_dense_extract)."""
            with jax.named_scope("win.commit"):
                state = _obs_win_bump(
                    state, obs_mod.WIN_WINDOWS, obs_mod.WIN_MATRIX
                )
            with jax.named_scope("win.extract"):
                pool = state.pool
                dense, tail = _dense_extract(
                    pool, win_start, win_end, H, K, PP, lrow=_lrow
                )
                d_t, d_s, d_q = dense.time, dense.src, dense.seq
                d_p = dense.payload
                # fillers interleave with real same-host rows only at time
                # NEVER, so a dense cell is real iff its time is set
                valid = d_t != NEVER
                nvalid = jnp.sum(valid, axis=1, dtype=jnp.int32)
            with jax.named_scope("win.handlers"):
                if with_cpu_model:
                    # CPU serialization as a scan (same semantics as the loop
                    # path's per-event chain): exec_k = max(t_k, exec_{k-1} +
                    # cost). With u_k = exec_k - k*cost this is a cummax of
                    # (t_k - k*cost) floored at cpu_avail.
                    cost = state.host.cpu_cost[:, None]  # [H, 1]
                    ks = jnp.arange(valid.shape[1], dtype=jnp.int64)[None, :]
                    shifted = jnp.where(
                        valid, d_t - ks * cost, jnp.int64(-(1 << 62))
                    )
                    u = jax.lax.cummax(shifted, axis=1)
                    u = jnp.maximum(u, state.host.cpu_avail[:, None])
                    exec_t = jnp.where(valid, u + ks * cost, d_t)
                    last_exec = soa.get_at(
                        exec_t, jnp.maximum(nvalid - 1, 0)
                    )
                    state = state.replace(
                        host=state.host.replace(
                            cpu_avail=jnp.where(
                                nvalid > 0,
                                last_exec + state.host.cpu_cost,
                                state.host.cpu_avail,
                            )
                        ),
                        counters=state.counters.replace(
                            cpu_delay_applied=state.counters.cpu_delay_applied
                            + jnp.sum(
                                jnp.where(valid, exec_t - d_t, 0),
                                dtype=jnp.int64,
                            )
                        ),
                    )
                else:
                    exec_t = d_t
                mv = MatrixEventView(
                    mask=valid, time=exec_t, src=d_s, seq=d_q,
                    payload=soa.unpack_words(d_p, P),
                )
                memit = MatrixEmitter()
                state = matrix_handlers[bulk_kind](state, mv, memit, params)
            with jax.named_scope("win.commit"):
                last_t = jnp.max(
                    jnp.where(valid, exec_t, jnp.int64(-1)), axis=1
                )
                done_t = jnp.where(nvalid > 0, last_t, state.host.done_t)
                state = state.replace(host=state.host.replace(done_t=done_t))
                # per-source sequence numbers: per host, emissions are
                # ordered column-major (event order), record-minor within a
                # column — identical to the loop path's per-event record
                # order
                base = state.host.seq_next
                masks = [r.mask.astype(jnp.int32) for r in memit.records]
                per_col = (
                    sum(masks) if masks else jnp.zeros((H, K), jnp.int32)
                )
                col_excl = jnp.cumsum(per_col, axis=1) - per_col
                seen = jnp.zeros((H, K), dtype=jnp.int32)
                # per record: (time, dst, src, seq, kind, pcols)
                em_rows = []
                hostsK = jnp.broadcast_to(gid[:, None], (H, K))
                for j, r in enumerate(memit.records):
                    seqj = base[:, None] + col_excl + seen
                    seen = seen + masks[j]
                    rp = soa.pack_words(r.payload)  # [H, K, PP]
                    em_rows.append((
                        jnp.where(r.mask, r.time, NEVER).reshape(-1),
                        r.dst.reshape(-1),
                        hostsK.reshape(-1),
                        seqj.reshape(-1),
                        r.kind.reshape(-1),
                        [rp[:, :, w].reshape(-1) for w in range(PP)],
                    ))
                total = jnp.sum(per_col, axis=1, dtype=jnp.int32)
                state = state.replace(
                    host=state.host.replace(seq_next=base + total)
                )
                # bulk-contract check (make_window_step docstring): the
                # matrix path is only sound if no emission targets SELF below
                # win_end — such an emission would deserve to interleave
                # with this window's batched events. Count violations
                # loudly.
                viol = jnp.zeros((), jnp.int64)
                for r in memit.records:
                    viol = viol + jnp.sum(
                        r.mask & (r.dst == hostsK) & (r.time < win_end),
                        dtype=jnp.int64,
                    )
                state = state.replace(
                    counters=state.counters.replace(
                        bulk_contract_violations=(
                            state.counters.bulk_contract_violations + viol
                        )
                    )
                )
                state = state.replace(
                    counters=state.counters.replace(
                        events_committed=state.counters.events_committed
                        + jnp.sum(valid, dtype=jnp.int64),
                        events_emitted=state.counters.events_emitted
                        + jnp.sum(per_col, dtype=jnp.int64),
                        micro_steps=state.counters.micro_steps + 1,
                    )
                )
                if state.obs is not None:
                    ob = state.obs
                    hd = ob.host_digest
                    if audit:
                        # audit chain over the dense window, column by
                        # column — per-host key order, identical to the loop
                        # path's micro-step commit order, so either dispatch
                        # path of the same window folds the same chain
                        for j in range(K):
                            hd = audit_mod.fold(
                                hd, valid[:, j], d_t[:, j], d_s[:, j], gid,
                                dense.kind[:, j],
                            )
                    state = state.replace(obs=ob.replace(
                        host_events=ob.host_events
                        + jnp.sum(valid, axis=1, dtype=jnp.int64),
                        host_last_t=jnp.where(
                            nvalid > 0, last_t, ob.host_last_t
                        ),
                        host_digest=hd,
                    ))
                if state.flight is not None:
                    fl = state.flight
                    for j in range(K):
                        fl = flight_mod.record(
                            fl, valid[:, j], d_t[:, j], d_s[:, j],
                            d_q[:, j], dense.kind[:, j],
                        )
                    state = state.replace(flight=fl)
            with jax.named_scope("win.merge"):
                # --- merge (sort 3): tail leftovers ∪ emissions, ONE 1-key
                # stable sort by time carrying every column; no payload
                # indirection gathers. Output truncates to pool capacity
                # (fillers sit at time NEVER and fall off first). ---
                m_t = jnp.concatenate([tail.time] + [e[0] for e in em_rows])
                m_d = jnp.concatenate([tail.dst] + [e[1] for e in em_rows])
                m_s = jnp.concatenate([tail.src] + [e[2] for e in em_rows])
                m_q = jnp.concatenate([tail.seq] + [e[3] for e in em_rows])
                m_k = jnp.concatenate([tail.kind] + [e[4] for e in em_rows])
                m_p = [
                    jnp.concatenate(
                        [tail.payload[w]] + [e[5][w] for e in em_rows]
                    )
                    for w in range(PP)
                ]
                state, arrival_min = assemble(
                    state, m_t, m_d, m_s, m_q, m_k, m_p
                )
            with jax.named_scope("win.commit"):
                # speculation-violation signal (optimistic synchronizer):
                # the one place a by-dst lookup is unavoidable; emissions are
                # the only candidate violators (leftovers already lived in
                # the pool)
                if em_rows and (island is None or island.optimistic):
                    e_t = jnp.concatenate([e[0] for e in em_rows])
                    e_d = jnp.concatenate([e[1] for e in em_rows])
                    e_s = jnp.concatenate([e[2] for e in em_rows])

                    def _exact(_):
                        # the one unavoidable by-dst lookup (a serialized
                        # gather on TPU) — only reached when a violation is
                        # even possible, i.e. under optimistic long windows
                        done_t = state.host.done_t
                        if island is None:
                            dst_last = done_t[jnp.clip(e_d, 0, H - 1)]
                            viol = (
                                (e_d != e_s) & (e_t != NEVER)
                                & (e_t <= dst_last)
                            )
                        else:
                            # local-dst only; foreign emissions are covered
                            # by assemble's arrival check on the owner shard
                            lr = _box_lrow(e_d)
                            loc = (lr >= 0) & (lr < H)
                            dst_last = done_t[jnp.clip(lr, 0, H - 1)]
                            viol = (
                                (e_d != e_s) & loc & (e_t != NEVER)
                                & (e_t <= dst_last)
                            )
                        return jnp.min(jnp.where(viol, e_t, NEVER))

                    possible = jnp.min(e_t) <= jnp.max(state.host.done_t)

                    def _never(_):
                        never = jnp.asarray(NEVER, jnp.int64)
                        if island is not None:
                            # under shard_map the true branch's output varies
                            # over the islands axis; the constant must be cast
                            # to the same varying type or cond rejects it
                            never = jax.lax.pcast(
                                never, (island.axis,), to="varying"
                            )
                        return never

                    xmit_min = jax.lax.cond(possible, _exact, _never, 0)
                else:
                    xmit_min = jnp.asarray(NEVER, jnp.int64)
                state = state.replace(
                    xmit_min=jnp.minimum(xmit_min, arrival_min)
                )
            with jax.named_scope("win.merge"):
                return state, jnp.min(state.pool.time)

        if bulk_kind is None or bulk_kind not in matrix_handlers:
            return run_loop(state)
        if _force_path == "matrix":
            return run_matrix(state)
        if _force_path == "loop":
            return run_loop(state)
        pool0 = state.pool
        inwin = pool0.time < win_end
        all_bulk = jnp.all(~inwin | (pool0.kind == bulk_kind))
        return jax.lax.cond(all_bulk, run_matrix, run_loop, state)

    return step


# ---------------------------------------------------------------------------
# Driver kernel factories
# ---------------------------------------------------------------------------
#
# Module-level so the fleet runner (shadow_tpu/fleet) can vmap them over a
# leading JOB axis: every argument that varies per job (runahead, stop) is a
# traced value, never a closed-over Python constant. The Simulation methods
# below delegate here with their own runahead baked in.


def make_run_to(step, hi: int):
    """Build run_to(state, params, runahead, stop, max_windows) ->
    (state, min_next, pressed, occupancy): the fused conservative window
    loop of the single-pool engine. `runahead` and `stop` are traced (the
    fleet passes per-job values); `hi` is the bound gear's red-zone mark
    (a compile-time int — every fleet lane shares the compiled pool
    shape, so it is shared too)."""

    def run_to(state: SimState, params: NetParams, runahead, stop,
               max_windows):
        """Advance up to max_windows windows (or until stop). Bounding
        the on-device while_loop keeps each dispatch short — long single
        dispatches can trip accelerator-runtime watchdogs.

        Exits early (third return value True) when pool occupancy
        crosses the spill red zone — the mark is PER-GEAR (`hi` is the
        bound gear's) — so the driver can upshift, or drain overflow to
        host memory BEFORE the merge would drop rows (core/spill.py) —
        one compare per window, no extra sorts. The final occupancy
        rides back as the fourth value: it is the gearing decision
        signal, fetched on the sync the driver already pays."""
        runahead = jnp.asarray(runahead, jnp.int64)
        stop = jnp.asarray(stop, jnp.int64)
        max_windows = jnp.asarray(max_windows, jnp.int32)

        def cond(c):
            state, mn, w = c
            occ = jnp.sum(state.pool.time != NEVER)
            return (mn < stop) & (w < max_windows) & (occ < hi)

        def body(c):
            state, mn, w = c
            ws = mn
            we = jnp.minimum(ws + runahead, stop)
            state, mn = step(state, params, ws, we)
            return state, mn, w + 1

        mn0 = jnp.min(state.pool.time)
        state, mn, _ = jax.lax.while_loop(
            cond, body, (state, mn0, jnp.int32(0))
        )
        occ = jnp.sum(state.pool.time != NEVER)
        return state, mn, occ >= hi, occ

    return run_to


def make_attempt(step):
    """Build attempt(state, params, ws, we) -> (state, min_next, viol):
    one optimistic window processed to completion ON DEVICE. All four
    arguments are traced, so the factory is directly vmappable over a
    leading job axis (the fleet's per-lane speculative windows)."""

    def attempt(state: SimState, params: NetParams, ws, we):
        """Process the window [ws, we) to completion: sub-step until no
        pool events remain below we, or a speculation violation surfaces
        (state.xmit_min != NEVER). One dispatch per attempt."""
        ws = jnp.asarray(ws, jnp.int64)
        we = jnp.asarray(we, jnp.int64)

        def cond(c):
            _, mn, v = c
            return (mn < we) & (v == simtime.NEVER)

        def body(c):
            st, mn, _ = c
            st2, mn2 = step(st, params, jnp.maximum(mn, ws), we)
            return st2, mn2, st2.xmit_min

        mn0 = jnp.min(state.pool.time)
        return jax.lax.while_loop(
            cond, body, (state, mn0, jnp.asarray(simtime.NEVER, jnp.int64))
        )

    return attempt


def _counters_dict(c) -> dict[str, int]:
    """A host copy of the Counters struct as {field: int}, by name."""
    return {k: int(v) for k, v in sorted(c.__dict__.items())}


# ---------------------------------------------------------------------------
# Simulation driver (controller/manager analog)
# ---------------------------------------------------------------------------


class Simulation:
    """Owns the built state + jitted kernels and plays the round loop.

    Construct via shadow_tpu.sim.build_simulation (from a Config) or directly
    with prebuilt pieces for tests.
    """

    def __init__(
        self,
        *,
        num_hosts: int,
        handlers: dict[int, Handler],
        params: NetParams,
        host_vertex: np.ndarray,
        seed: int,
        stop_time: int,
        runahead: int,
        event_capacity: int = 1 << 14,
        K: int = 32,
        B: int = 8,
        O: int = 64,
        subs: dict | None = None,
        initial_events: list[tuple[int, int, int, int, list[int]]] | None = None,
        bulk_kinds: dict[int, int] | None = None,
        matrix_handlers: dict[int, Callable] | None = None,
        payload_words: int = PAYLOAD_WORDS,
        cpu_ns_per_event: np.ndarray | None = None,
        bulk_gate: Callable | None = None,
        bulk_self_excluded: bool = False,
        obs_counters: bool = True,
        pool_gears: int = 1,
        audit_digest: bool = True,
        flight_capacity: int = 0,
        pipelined_dispatch: bool = True,
        host_workers: int = 1,
    ):
        # initial_events: (time, dst, src, kind, payload words)
        self.num_hosts = num_hosts
        self.stop_time = int(stop_time)
        self.runahead = int(runahead)
        if self.runahead <= 0:
            raise ValueError("runahead must be > 0 (min topology latency)")
        self.params = params
        n0 = len(initial_events or [])
        if n0 > event_capacity:
            raise ValueError("initial events exceed event pool capacity")
        # Occupancy-adaptive pool gearing (core/gearbox.py): a ladder of
        # (capacity, dense width) tiers, each compiling its own window
        # kernel; drivers shift at dispatch boundaries. pool_gears=1 keeps
        # a single tier at the configured shapes — the pre-gearbox build.
        self.pool_gears = int(pool_gears)
        self._gear_ladder = gearbox.build_ladder(
            self.pool_gears, event_capacity, K, num_hosts, spill_mod.marks
        )
        self._gear = (
            gearbox.target_level(self._gear_ladder, n0)
            if len(self._gear_ladder) > 1
            else self._gear_ladder[-1].level
        )
        self._shifter = (
            gearbox.GearShifter(self._gear_ladder)
            if len(self._gear_ladder) > 1
            else None
        )
        self._gear_shifts = 0
        self._gear_dispatches: dict[int, int] = {}
        pool = EventPool.empty(
            self._gear_ladder[self._gear].capacity, payload_words
        )
        if initial_events:
            # Assign per-source sequence numbers in list order, like the
            # reference assigns per-source event IDs at push time.
            seq_ctr: dict[int, int] = {}
            times, dsts, srcs, seqs, kinds_, pls = [], [], [], [], [], []
            for (t, d, s, k, pl) in initial_events:
                q = seq_ctr.get(s, 0)
                seq_ctr[s] = q + 1
                times.append(t)
                dsts.append(d)
                srcs.append(s)
                seqs.append(q)
                kinds_.append(k)
                row = list(pl) + [0] * (payload_words - len(pl))
                pls.append(row[:payload_words])
            sl = slice(0, n0)
            pool = pool.replace(
                time=pool.time.at[sl].set(jnp.asarray(times, jnp.int64)),
                dst=pool.dst.at[sl].set(jnp.asarray(dsts, jnp.int32)),
                src=pool.src.at[sl].set(jnp.asarray(srcs, jnp.int32)),
                seq=pool.seq.at[sl].set(jnp.asarray(seqs, jnp.int32)),
                kind=pool.kind.at[sl].set(jnp.asarray(kinds_, jnp.int32)),
                payload=pool.payload.at[sl].set(
                    soa.pack_words(jnp.asarray(pls, jnp.int32))
                ),
            )
            seq_init = np.zeros(num_hosts, dtype=np.int32)
            for s, q in sorted(seq_ctr.items()):
                seq_init[s] = q
        else:
            seq_init = np.zeros(num_hosts, dtype=np.int32)

        self.handlers = handlers
        self.K, self.B, self.O = K, B, O
        with_cpu = cpu_ns_per_event is not None and bool(
            np.any(np.asarray(cpu_ns_per_event) > 0)
        )
        # Stash the kernel build config so parallel/islands.py (and any
        # other re-wiring subclass) can rebuild the window step with a
        # different execution layout.
        self._bulk_kinds = bulk_kinds
        self._matrix_handlers = matrix_handlers
        self._with_cpu = with_cpu
        self._bulk_gate = bulk_gate
        self._bulk_self_excluded = bulk_self_excluded
        self._payload_words = payload_words
        # Determinism audit plane (obs/audit.py): the digest chain folds
        # ride the obs block; False compiles the folds out — the control
        # arm of bench.py --audit-smoke.
        self._audit_digest = bool(audit_digest)
        host = make_host_state(
            num_hosts, host_vertex,
            cpu_cost=cpu_ns_per_event if with_cpu else None,
        )
        host = host.replace(seq_next=jnp.asarray(seq_init))
        self.state = SimState(
            now=jnp.int64(0),
            pool=pool,
            host=host,
            counters=Counters.zeros(),
            rng_keys=rng_mod.host_keys(seed, num_hosts),
            subs=subs or {},
            obs=obs_mod.ObsBlock.zeros(num_hosts) if obs_counters else None,
            flight=(
                flight_mod.FlightRing.zeros(num_hosts, int(flight_capacity))
                if flight_capacity else None
            ),
        )
        # Telemetry session (obs/metrics.ObsSession): attached by the CLI
        # (--metrics-out/--trace-out) or bench; None keeps the run loops on
        # their zero-instrumentation path.
        self.obs_session = None
        # The committed Counters as the fused driver fetched them with its
        # dispatch, held on the host while the session reads them at the
        # boundary (obs/counters.committed); None everywhere else.
        self.boundary_counters = None
        # Determinism-audit trail + flight spool (obs/audit.py /
        # obs/flight.py): attached by --digest-out / --flight-out; None
        # keeps every handoff free of the extra obs-block fetch.
        self.audit = None
        self.flight_spool = None
        # Fault-tolerance plane (shadow_tpu/faults): device/file injections
        # execute at handoff boundaries via _handoff_tick; quarantined
        # (dead) hosts have their pending pool/spill events drained at
        # every subsequent handoff — the crashed-host semantic. Auto-
        # checkpointing (--checkpoint-every) rides the same tick.
        self.fault_injector = None
        self._dead_hosts: set[int] = set()
        self._force_spill = False
        # Backend supervision (core/supervisor.py): every driver dispatch
        # routes through _sv(); with no supervisor attached that is a
        # direct call — zero overhead, pre-supervisor behavior. The
        # failover flag re-lowers kernels on the CPU backend (_jit).
        self.supervisor = None
        self._cpu_failover = False
        # Pipelined CPU↔TPU handoff (core/pipeline.py): the drivers
        # double-buffer dispatches — issue window N+1 asynchronously
        # while the host drains window N — synchronizing only at the
        # fetch point. experimental.pipelined_dispatch: false restores
        # the strictly-serial loop (the bench comparison arm). Stats are
        # created lazily so serial runs emit no pipeline.* keys.
        self.pipelined_dispatch = bool(pipelined_dispatch)
        self._pipeline_stats: dict | None = None
        # Host handoff hooks: called as fn(sim, frontier_ns) inside every
        # driver's host-drain phase (after the fault/checkpoint tick) —
        # the seam for host-side per-handoff work the pipeline overlaps
        # (the managed-plane syscall-drain analog; bench models it here).
        # Entries are (fn, sharded): sharded hooks take (sim, frontier_ns,
        # gid) per owning host and drain through the multi-worker host
        # plane below.
        self._handoff_hooks: list = []
        # PARSIR-style multi-worker host plane (core/hostplane.py): with
        # experimental.host_workers > 1 the per-host handoff actions
        # (sharded hooks, flight-spool extraction) fan out to pinned
        # workers and merge in canonical (virtual-time, host-gid) order —
        # bit-exact vs the serial drain by construction, and the drain
        # runs inside the pipeline's issue->await overlap window. 1 (the
        # default) keeps today's strictly-serial inline drain: no
        # threads, and no hostplane.* stats keys.
        self.host_workers = max(1, int(host_workers))
        self._hostplane_obj = None
        self._hostplane_stats: dict | None = None
        self._hostplane_slot_cache: tuple | None = None
        # Elastic mesh resilience (parallel/elastic.py): the runner's
        # dispatch-boundary hook — probes lost chips and signals the
        # relayout-back-up. None = one attribute check per dispatch.
        self.elastic = None
        # Resource-pressure plane (core/pressure.py): None until the
        # first pressure signal (a stall, an XLA RESOURCE_EXHAUSTED, or a
        # saturate_pool injection) lazily attaches the default ladder —
        # the no-pressure path stays attribute-check cheap. Reshaping
        # ladder rungs (gear downshift) are forbidden while an optimistic
        # attempt holds a rollback snapshot of the current shapes.
        self.pressure = None
        self._pressure_reshape_ok = True
        self.checkpoint_dir: str | None = None
        self.checkpoint_every_ns = 0
        self.checkpoint_retain = 3
        self._ckpt_next_t = 0
        self._ckpt_seq = 0
        self.fault_counters = {
            "hosts_quarantined": 0,
            "events_drained": 0,
            "files_corrupted": 0,
            "checkpoints_written": 0,
            "checkpoints_pruned": 0,
            "resume_fallbacks": 0,
        }
        self._gear_fns: dict[int, dict] = {}
        self._bind_gear()

    # -- gearbox plumbing (core/gearbox.py): one compiled kernel set per
    # active gear, bound into the attributes every driver (and test, and
    # procs.bridge) already reads --
    def _build_gear_fns(self, spec: gearbox.GearSpec) -> dict:
        step = make_window_step(
            self.handlers, self.num_hosts, K=spec.K, B=self.B, O=self.O,
            bulk_kinds=self._bulk_kinds,
            matrix_handlers=self._matrix_handlers,
            with_cpu_model=self._with_cpu,
            bulk_gate=self._bulk_gate,
            bulk_self_excluded=self._bulk_self_excluded,
            payload_words=self._payload_words,
            audit=self._audit_digest,
        )
        return {
            "step_fn": step,
            "step": self._jit(step),
            "run_to": self._jit(self._make_run_to(step, spec.hi)),
            "attempt": self._jit(self._make_attempt(step)),
        }

    def _jit(self, fn):
        """jit honoring degraded-mode failover (core/supervisor.py): with
        the supervisor in CPU failover, kernels re-lower on the CPU
        backend so the simulation keeps advancing while the accelerator
        is gone; the default path is a plain jax.jit."""
        jf = jax.jit(fn)
        if not getattr(self, "_cpu_failover", False):
            return jf
        try:
            dev = jax.devices("cpu")[0]
        except RuntimeError:
            return jf

        def on_cpu(*args):
            with jax.default_device(dev):
                return jf(*args)

        return on_cpu

    def _bind_gear(self) -> None:
        spec = self._gear_ladder[self._gear]
        fns = self._gear_fns.get(spec.level)
        if fns is None:
            fns = self._gear_fns[spec.level] = self._build_gear_fns(spec)
        # raw (unjitted) step for callers composing their own fused device
        # loops (e.g. procs.bridge's run-until-output sync loop)
        self._step_fn = fns["step_fn"]
        self._step = fns["step"]
        self._run_to = fns["run_to"]
        self._attempt = fns["attempt"]

    def _shift_gear(self, level: int) -> None:
        """Move the pool to `level`'s capacity (one truncating/padding
        re-sort — gearbox.resize_pool) and rebind that gear's compiled
        kernels. Handoff-boundary only: never inside a jitted window loop,
        and never inside an optimistic attempt (rollback snapshots must
        keep their shapes)."""
        spec = self._gear_ladder[level]
        pool, dropped = gearbox.resize_pool(self.state.pool, spec.capacity)
        self.state = self.state.replace(
            pool=pool,
            counters=self.state.counters.replace(
                pool_overflow_dropped=(
                    self.state.counters.pool_overflow_dropped + dropped
                )
            ),
        )
        self._gear = level
        self._gear_shifts += 1
        if self._shifter is not None:
            self._shifter.reset()
        self.state = obs_mod.bump_win(self.state, obs_mod.WIN_GEAR_SHIFTS)
        obs = getattr(self, "obs_session", None)
        if obs is not None and obs.tracer:
            obs.tracer.instant(
                "gear_shift", level=level, capacity=spec.capacity
            )
        self._bind_gear()

    def _gear_tick(self, occ: int, press: bool = False,
                   margin: int = 1) -> bool:
        """One dispatch-boundary gearing decision; returns True iff the
        gear changed. No-op (and no occupancy math) on ungeared builds."""
        if self._shifter is None:
            return False
        if self.pressure is not None and self.pressure.hold_gear:
            # forced-downshift hold (pressure ladder): the red-zone
            # upshift rule is overridden while device memory is tight —
            # the spill tier absorbs the occupancy instead
            return False
        new = self._shifter.observe(
            self._gear, int(occ), press=press, margin=margin
        )
        if new is None:
            return False
        self._shift_gear(new)
        return True

    def _gear_note_dispatch(self) -> None:
        self._gear_dispatches[self._gear] = (
            self._gear_dispatches.get(self._gear, 0) + 1
        )

    def _live_spill_clamp(self, stop_at: int, wpd: int) -> tuple[int, int]:
        """Call-time spill clamp for SUPERVISED dispatch thunks: a
        pressure-ladder rung (forced downshift) can engage the spill tier
        BETWEEN attempts of one dispatch, after the driver computed its
        stop time — the retry must then clamp below the earliest parked
        row (and drop to single-window dispatches) or resident hosts
        would process past host-parked events and diverge from the
        oversized-pool run. Identity while the spill tier is empty."""
        sp = getattr(self, "_spill", None)
        if sp is None or not sp.count:
            return stop_at, wpd
        return (
            min(stop_at, sp.min_time + self.runahead, min(sp._partial_min)),
            1,
        )

    def _pool_occupancy(self) -> int:
        """Live pool rows — the gearing decision signal for the stepwise
        and optimistic drivers (the fused driver gets it for free on the
        run_to sync). One small reduce + fetch per dispatch boundary, paid
        only on geared builds."""
        return int(jnp.sum(self.state.pool.time != NEVER))

    def gear_stats(self) -> dict:
        """Gearbox telemetry for bench rows / metrics dumps: active level,
        ladder shape, shift count, and the per-gear dispatch histogram."""
        spec = self._gear_ladder[self._gear]
        return {
            "gear_level": self._gear,
            "gear_tiers": len(self._gear_ladder),
            "gear_capacity": spec.capacity,
            "gear_k": spec.K,
            "gear_shifts": self._gear_shifts,
            "gear_dispatches": {
                str(lvl): n for lvl, n in sorted(self._gear_dispatches.items())
            },
        }

    def _import_foreign_layout(self, foreign, meta) -> None:
        """checkpoint.restore_relayout hook: adopt a checkpoint taken in
        the islands [S, ...] layout into this GLOBAL build — the
        partition collapses (host rows land by gid, pool rows compact,
        per-shard counters sum). Per-host order, RNG streams and the
        audit digest key on global host ids, so the resumed run extends
        the checkpointed chain exactly. Routes into the CURRENT gear's
        pool; overflow raises with the capacity hint."""
        from shadow_tpu.parallel import islands as islands_mod

        self.state = islands_mod.globalize_state(
            foreign, int(self.state.pool.time.shape[-1])
        )

    def _make_run_to(self, step, hi: int):
        lane = make_run_to(step, hi)
        runahead = jnp.int64(self.runahead)

        def run_to(state: SimState, params: NetParams, stop, max_windows):
            return lane(state, params, runahead, stop, max_windows)

        return run_to

    # -- host-driven round loop (one device sync per window; debuggable) --

    def _step_halves(self, ws: int, we: int):
        """(issue_fn, fetch_fn) halves of one stepwise window dispatch.
        issue enqueues the jitted step (async — device futures); fetch
        performs the blocking frontier read. A supervised retry re-runs
        both halves, re-reading the bound kernel and re-clamping the
        spill stop per attempt — exactly what the fused thunk did."""

        def issue(ws=ws, we=we):
            we, _ = self._live_spill_clamp(we, 1)
            return self._step(self.state, self.params, ws, max(ws, we))

        def fetch(out):
            st, mn = out
            return st, int(mn)

        return issue, fetch

    def run_stepwise(self, until: int | None = None) -> int:
        stop = self.stop_time if until is None else min(until, self.stop_time)
        spill = self._spill_store()
        obs = self.obs_session
        pipe = self._pipeline()
        windows = 0
        stall = 0
        # Committed frontier carried from the dispatch's own return value:
        # re-deriving it with a fresh jnp.min per handoff dispatched one
        # tiny reduce kernel per window for nothing. None = must derive
        # from the pool (startup, or after a tick mutated it).
        min_next = None
        try:
            while True:
                if self._shifter is not None:
                    # gear decision BEFORE spill manage: an upshift absorbs
                    # red-zone pressure without a host drain episode
                    self._gear_tick(self._pool_occupancy())
                with metrics_mod.span(obs, "spill"):
                    tok = self.state
                    stop_at = spill_mod.manage(self, spill, stop)
                if self.state is not tok or min_next is None:
                    min_next = int(jnp.min(self.state.pool.time))
                if self._fault_plane_active():
                    tok = self.state
                    self._handoff_tick(min_next)
                    if self.state is not tok:
                        # a drain may have removed the frontier event
                        min_next = int(jnp.min(self.state.pool.time))
                if min_next >= stop_at:
                    if min_next >= stop and spill.min_time >= stop:
                        break
                    stall += 1
                    if stall > 2:
                        occ = self._pool_occupancy()
                        cap = self._gear_ladder[self._gear].capacity
                        if self._pressure_stall(window=min_next,
                                                occupancy=occ,
                                                capacity=cap):
                            stall = 0  # a ladder rung reshaped the tier
                            continue
                        raise self._pool_exhausted(
                            "spill tier cannot make progress: either a "
                            "single timestamp holds more events than the "
                            "pool fill mark, or pool occupancy leaves too "
                            "little headroom for even one window's "
                            "emissions (the pool-headroom gate stalled "
                            "every host); raise "
                            "experimental.event_capacity",
                            window=min_next, occupancy=occ, capacity=cap,
                        )
                    continue
                stall = 0
                if self.pressure is not None:
                    self.pressure.note_progress()
                ws = min_next
                we = min(ws + self.runahead, stop_at)
                # adopt the issued-ahead window iff the committed state
                # and args are exactly what the serial loop would pass
                # (core/pipeline.py recompute rule)
                pending = (
                    pipe.take(self.state, (ws, we))
                    if pipe is not None else None
                )
                if pending is None:
                    with metrics_mod.span(obs, "dispatch", windows=1):
                        p = self._sv_issue(
                            "step", *self._step_halves(ws, we)
                        )
                        self.state, mn = self._sv_await(p)
                else:
                    with metrics_mod.span(obs, "await", windows=1):
                        self.state, mn = self._sv_await(pending)
                self._gear_note_dispatch()
                min_next = mn
                # two-slot pipeline: issue window N+1 before draining
                # window N's handoff — only across a quiet boundary
                if pipe is not None and mn < stop:
                    if (not spill.count and not self._force_spill
                            and self._handoff_quiet(mn)
                            and not self._sv_disrupted()):
                        ws2, we2 = mn, min(mn + self.runahead, stop)
                        with metrics_mod.span(obs, "issue", windows=1):
                            pipe.put(
                                self._sv_issue(
                                    "step", *self._step_halves(ws2, we2)
                                ),
                                self.state, (ws2, we2),
                            )
                    else:
                        pipe.forced_drain()
                with metrics_mod.span(obs, "host_drain"):
                    if self._audit_active():
                        self._audit_tick(mn)
                    self._run_handoff_hooks(mn)
                if pipe is not None:
                    if self._sv_disrupted():
                        pipe.discard()
                    else:
                        pipe.invalidate(self.state)
                windows += 1
        finally:
            if pipe is not None:
                pipe.close()
        return windows

    def _make_attempt(self, step):
        return make_attempt(step)

    # -- optimistic synchronization: speculate long windows, roll back on
    # violation (SURVEY §7.6). Pure-array state makes rollback free: the
    # pre-window state is just the previous pytree. --
    @staticmethod
    def adapt_window_factor(
        factor: int, streak: int, rolled_back: bool, cap: int
    ) -> tuple[int, int]:
        """The Time-Warp throttling policy shared by the global and
        islands optimistic drivers: halve the speculation factor on a
        rolled-back window, double it after four clean windows in a row.
        Per-run deterministic (depends only on sim state, never wall
        time)."""
        if rolled_back:
            return max(1, factor // 2), 0
        streak += 1
        if streak >= 4 and factor < cap:
            return min(cap, factor * 2), 0
        return factor, streak

    def run_optimistic(
        self,
        until: int | None = None,
        window_factor: int = 8,
        adaptive: bool = True,
    ) -> tuple[int, int]:
        """Advance with speculative windows of window_factor × runahead.

        A window [ws, we) is processed to completion by repeated sub-steps
        (each processes all pool events < we in per-host key order; newly
        generated cross-host deliveries inside the window are picked up by
        the following sub-step). `host.done_t` tracks each host's processed
        progress across sub-steps; a sub-step reports a violation
        (state.xmit_min < NEVER) when it emitted a delivery behind its
        destination's progress clock. On violation the WHOLE window rolls
        back to the snapshot (pure arrays — rollback is just dropping the
        speculated pytree) and retries with the window shrunk to the
        violation time, never below the conservative runahead, which is
        violation-free by construction (emission time >= now + min_latency
        >= ws + runahead >= any processed time).

        With ``adaptive`` (BASELINE config 4's "optimistic PDES windows"
        tuning), the factor self-regulates between 1 and window_factor: a
        rolled-back window halves it (speculation is outrunning the
        workload's lookahead), four clean windows in a row double it —
        the standard Time-Warp throttling shape, per-run deterministic
        (the schedule depends only on sim state, never wall time).

        Returns (windows_committed, rollbacks). Produces the conservative
        schedule's results; wins when the pool holds work spanning many
        runaheads (fewer barriers/dispatches per simulated second).
        """
        stop = self.stop_time if until is None else min(until, self.stop_time)
        cons = self.runahead
        windows = rollbacks = 0
        factor = window_factor
        streak = 0
        neg1 = jnp.full((self.num_hosts,), -1, dtype=jnp.int64)
        self.state = self.state.replace(
            host=self.state.host.replace(done_t=neg1)
        )
        obs = self.obs_session
        pipe = self._pipeline()
        min_next = int(jnp.min(self.state.pool.time))
        try:
            while min_next < stop:
                if self._shifter is not None:
                    # margin=2: a speculative window absorbs several
                    # windows' inflow between decision points, so gear
                    # selection keeps double headroom
                    # (core/gearbox.target_level)
                    self._gear_tick(self._pool_occupancy(), margin=2)
                ws = min_next
                we = min(ws + factor * cons, stop)
                base = self.state  # rollback snapshot (done_t reset)
                rb0 = rollbacks
                # pressure-ladder rungs that reshape the pool (gear
                # downshift) are forbidden while `base` pins the compiled
                # shapes; non-reshaping rungs (spill-fill escalation) stay
                # available to the supervisor's RESOURCE_EXHAUSTED retries
                self._pressure_reshape_ok = False
                # adopt the issued-ahead first attempt iff base + window
                # bounds are exactly the serial loop's (recompute rule)
                first = (
                    pipe.take(base, (ws, we)) if pipe is not None else None
                )
                with metrics_mod.span(obs, "window", factor=factor):
                    while True:  # attempt [ws, we); shrink on violation
                        if first is not None:
                            with metrics_mod.span(obs, "await"):
                                st, mn, viol = self._sv_await(first)
                            first = None
                        else:
                            with metrics_mod.span(obs, "dispatch"):
                                p = self._sv_issue(
                                    "attempt",
                                    *self._attempt_halves(base, ws, we),
                                )
                                st, mn, viol = self._sv_await(p)
                        self._gear_note_dispatch()
                        if we <= ws + cons and viol < int(simtime.NEVER):
                            # A conservative-width window is violation-free
                            # BY CONSTRUCTION (emission time >= ws +
                            # runahead >= any processed time). A violation
                            # here means the conservative-width invariant
                            # itself is broken — committing would silently
                            # accept a causally-violated window (ADVICE
                            # round-5 finding).
                            raise RuntimeError(
                                f"speculation violation at t={viol} inside "
                                f"a conservative-width window [{ws}, {we}): "
                                f"the conservative-width invariant is "
                                f"broken — runahead {cons} ns exceeds a "
                                f"real path latency "
                                f"({self._runahead_bound_hint()}), or a "
                                f"handler emitted into the past; refusing "
                                f"to commit"
                            )
                        if viol >= int(simtime.NEVER) or we <= ws + cons:
                            break
                        rollbacks += 1
                        if obs is not None and obs.tracer:
                            obs.tracer.instant("rollback", viol_ns=viol)
                        we = max(viol, ws + cons)
                # driver-plane telemetry bumps ride the state replace the
                # loop does anyway (handoff boundary — no sync added);
                # each rollback shrank the window once
                self._pressure_reshape_ok = True
                st = obs_mod.bump_win(
                    st, obs_mod.WIN_ROLLBACKS, rollbacks - rb0
                )
                st = obs_mod.bump_win(
                    st, obs_mod.WIN_SHRINKS, rollbacks - rb0
                )
                self.state = st.replace(host=st.host.replace(done_t=neg1))
                min_next = int(mn)
                windows += 1
                if adaptive:
                    # pure host arithmetic — computed at commit (before
                    # the speculative issue needs the next factor); the
                    # schedule is identical to the serial loop's
                    factor, streak = self.adapt_window_factor(
                        factor, streak, rollbacks > rb0, window_factor
                    )
                # two-slot pipeline: issue window N+1's first attempt
                # from the committed state before draining this handoff
                if pipe is not None and min_next < stop:
                    if (self._handoff_quiet(min_next)
                            and not self._sv_disrupted()):
                        ws2 = min_next
                        we2 = min(ws2 + factor * cons, stop)
                        with metrics_mod.span(obs, "issue"):
                            pipe.put(
                                self._sv_issue(
                                    "attempt",
                                    *self._attempt_halves(
                                        self.state, ws2, we2
                                    ),
                                ),
                                self.state, (ws2, we2),
                            )
                    else:
                        pipe.forced_drain()
                with metrics_mod.span(obs, "host_drain"):
                    if self.pressure is not None:
                        self.pressure.note_progress()
                    if obs is not None:
                        obs.round_done(self, min_next)
                    self._audit_tick(min_next)
                    if self._fault_plane_active():
                        self._handoff_tick(min_next)
                        min_next = int(jnp.min(self.state.pool.time))
                    self._run_handoff_hooks(min_next)
                if pipe is not None:
                    if self._sv_disrupted():
                        pipe.discard()
                    else:
                        pipe.invalidate(self.state)
        finally:
            if pipe is not None:
                pipe.close()
        return windows, rollbacks

    def _attempt_halves(self, base, ws: int, we: int):
        """(issue_fn, fetch_fn) halves of one optimistic attempt from
        the rollback snapshot `base` (captured explicitly — a supervised
        retry must re-speculate the same window from the same
        snapshot)."""

        def issue(base=base, ws=ws, we=we):
            return self._attempt(base, self.params, ws, we)

        def fetch(out):
            st, mn, viol = out
            return st, int(mn), int(viol)

        return issue, fetch

    def _runahead_bound_hint(self) -> str:
        """The actually-safe runahead bound for conservative-width
        violation errors: the minimum finite baked path latency. The
        islands engine overrides this with the partition-derived
        cross-shard lookahead (parallel/lookahead.py), naming the
        critical shard link."""
        lat = np.asarray(jax.device_get(self.params.latency_vv))
        finite = lat[lat < int(simtime.NEVER)]
        if finite.size == 0:
            return "the topology bakes no finite path latency"
        return (
            f"minimum baked topology path latency is {int(finite.min())} "
            f"ns; set experimental.runahead <= {int(finite.min())} ns"
        )

    # -- host-spill tier (core/spill.py): the pool never silently drops --
    def _spill_marks(self) -> tuple[int, int]:
        """(pressure mark, rebalance fill mark) in pool rows per shard —
        PER-GEAR: the active gear's capacity defines the red zone.
        Pressure must fire while the merge can still absorb one window's
        inflow; the fill mark sits below pressure so a rebalance —
        including a partially-resident giant host's admission — exits the
        red zone and the fused loop keeps running windows. The pressure
        plane (core/pressure.py) scales both marks: injected saturation
        shrinks them, and memory-ladder escalation halves the fill per
        notch — identity until a pressure event actually engaged."""
        spec = self._gear_ladder[self._gear]
        hi, fill = spec.hi, spec.fill
        cap = getattr(self, "_pressure_fill_cap", None)
        if cap is not None:
            # transient override during a forced downshift: park down to
            # the TARGET gear's fill before the pool re-sorts smaller
            fill = min(fill, cap)
        if self.pressure is not None:
            hi, fill = self.pressure.scaled_marks(hi, fill)
        return hi, fill

    def _spill_store(self):
        if getattr(self, "_spill", None) is None:
            from shadow_tpu.core import spill as spill_mod2

            t = self.state.pool.time
            S = t.shape[0] if t.ndim == 2 else 1
            self._spill = spill_mod2.HostSpill(
                S, self.state.pool.payload.shape[-1]
            )
        return self._spill

    def spill_stats(self) -> dict:
        return self._spill_store().stats()

    # -- fused run: windows execute in on-device while_loop chunks --

    def _run_to_halves(self, stop_at: int, wpd: int):
        """(issue_fn, fetch_fn) halves of one fused-loop dispatch. issue
        enqueues the run_to program (jax async dispatch — futures); fetch
        performs the blocking host reads. The supervisor re-runs BOTH for
        a retry: issue re-reads the bound kernels and re-clamps the spill
        stop per attempt, so recovery rebinds and mid-dispatch pressure
        rungs behave exactly as under the fused thunk."""

        def issue(stop_at=stop_at, wpd=wpd):
            # per-attempt clamp: a pressure rung may have engaged the
            # spill tier since the driver computed stop_at
            stop_at, wpd = self._live_spill_clamp(stop_at, wpd)
            return self._run_to(self.state, self.params, stop_at, wpd)

        def fetch(out):
            st, mn, press, occ = out
            obs = self.obs_session
            if obs is None:
                # blocking fetches INSIDE the supervised await: async-
                # dispatch errors must surface here, not at a later
                # unsupervised sync
                return st, int(mn), bool(press), int(occ), None
            # with a session: the device wait and the host reads are
            # spans of their own, and the committed counters ride the
            # same transfer, so round_done reads no device state
            with metrics_mod.span(obs, "wait"):
                jax.block_until_ready((mn, press, occ))
            with metrics_mod.span(obs, "fetch"):
                mn, press, occ, c = jax.device_get(
                    (mn, press, occ, st.counters)
                )
            return st, int(mn), bool(press), int(occ), _counters_dict(c)

        return issue, fetch

    def run(
        self, until: int | None = None, windows_per_dispatch: int = 64
    ) -> None:
        stop = self.stop_time if until is None else min(until, self.stop_time)
        spill = self._spill_store()
        obs = self.obs_session
        pipe = self._pipeline()
        last = None
        try:
            while True:
                active = (
                    (last is not None and last[2]) or spill.count
                    or self._force_spill  # injected force_spill fault
                )
                if active:
                    if pipe is not None:
                        # spill manage mutates the pool: a barrier point
                        # (the boundary was already tallied as a forced
                        # drain when speculation was skipped)
                        pipe.close()
                    with metrics_mod.span(obs, "spill"):
                        stop_at = spill_mod.manage(self, spill, stop)
                else:
                    stop_at = stop
                # whole-host spill residency is only exact with a manage
                # pass between consecutive windows (core/spill.py manage)
                wpd = 1 if spill.count else windows_per_dispatch
                if self._fault_plane_active():
                    # hand off at the next injection/checkpoint mark
                    stop_at = min(stop_at, self._fault_mark())
                # adopt the issued-ahead dispatch iff the committed state
                # and recomputed args are exactly what the serial loop
                # would pass now (core/pipeline.py recompute rule)
                pending = (
                    pipe.take(self.state, (stop_at, wpd))
                    if pipe is not None else None
                )
                if pending is None:
                    with metrics_mod.span(obs, "dispatch", windows=wpd):
                        p = self._sv_issue(
                            "run_to", *self._run_to_halves(stop_at, wpd)
                        )
                        self.state, mn, press, occ, held = self._sv_await(p)
                else:
                    with metrics_mod.span(obs, "await", windows=wpd):
                        self.state, mn, press, occ, held = self._sv_await(
                            pending
                        )
                # two-slot pipeline: issue dispatch N+1 asynchronously
                # BEFORE draining dispatch N's handoff — the device
                # computes while the host drains; state-mutating ticks
                # stay barrier points (forced_drain), and a drain that
                # mutates anyway discards the issue (recompute, never
                # reuse — the invalidate below)
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
                    if obs is not None:
                        self.boundary_counters = held
                        obs.round_done(self, mn)
                        self.boundary_counters = None
                    self._audit_tick(mn)
                    # gearing: a red-zone early exit upshifts (one pool
                    # re-sort) before the spill tier would pay host drain
                    # round-trips
                    shifted = self._gear_tick(occ, press=press)
                    if self._fault_plane_active():
                        self._handoff_tick(mn)
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
                cur = (mn, spill.count, press)
                if cur == last and mn >= stop_at and not shifted:
                    cap = self._gear_ladder[self._gear].capacity
                    if self._pressure_stall(window=mn, occupancy=occ,
                                            capacity=cap):
                        last = None  # a ladder rung reshaped the tier
                        continue
                    raise self._pool_exhausted(
                        "spill tier cannot make progress: either a single "
                        "timestamp holds more events than the pool fill "
                        "mark, or pool occupancy leaves too little "
                        "headroom for even one window's emissions (the "
                        "pool-headroom gate stalled every host); raise "
                        "experimental.event_capacity",
                        window=mn, occupancy=occ, capacity=cap,
                    )
                elif self.pressure is not None:
                    self.pressure.note_progress()
                last = cur
        finally:
            if pipe is not None:
                pipe.close()

    # -- fault-tolerance plane (shadow_tpu/faults) + auto-checkpointing --

    def attach_faults(self, faults) -> None:
        """Arm a parsed fault plan (list of faults.plan.Fault). Device and
        file ops execute at handoff boundaries; proc ops are not valid on
        the device plane (the builder/CLI routes those to ProcessDriver).
        Backend ops (kill_backend / stall_backend) drive the supervision
        state machine — a default supervisor (policy `abort`) is attached
        when the plan carries them and none is armed yet."""
        from shadow_tpu.faults import FaultInjector
        from shadow_tpu.faults import plan as plan_mod

        self.fault_injector = FaultInjector(faults) if faults else None
        if faults and self.supervisor is None and any(
            f.op in plan_mod.BACKEND_OPS for f in faults
        ):
            from shadow_tpu.core.supervisor import BackendSupervisor

            self.attach_supervisor(BackendSupervisor())

    # -- backend supervision (core/supervisor.py) --

    def attach_supervisor(self, supervisor) -> None:
        """Arm backend supervision: every subsequent driver dispatch goes
        through supervisor.call — deadline watchdog, classified retries,
        and drain-to-checkpoint + wait/cpu/abort recovery on loss."""
        supervisor.bind(self)
        self.supervisor = supervisor

    def _sv(self, label: str, thunk):
        """Run one dispatch thunk, supervised when a supervisor is
        attached (a direct call otherwise — the zero-overhead default)."""
        if self.supervisor is None:
            return thunk()
        return self.supervisor.call(label, thunk)

    def _sv_issue(self, label: str, issue_fn, fetch_fn):
        """The ISSUE half of a split dispatch: enqueue the device work
        (jax async dispatch — futures, no blocking) and return the
        ticket. Supervised when a supervisor is attached; a direct
        launch otherwise."""
        if self.supervisor is None:
            return PendingDispatch.direct(label, issue_fn, fetch_fn)
        return self.supervisor.issue(label, issue_fn, fetch_fn)

    def _sv_await(self, pending):
        """The AWAIT half: block on the ticket's host fetches. With a
        supervisor attached this runs the classified retry ladder,
        pressure rungs, watchdog and loss policies — all operating on
        the awaited half, so pipelining never re-serializes them."""
        if self.supervisor is None:
            return pending.await_direct()
        return self.supervisor.await_result(pending)

    def _sv_disrupted(self) -> bool:
        """True when the supervisor already knows the next dispatch will
        not run clean (injected kill/stall/exhaust, failover) — the
        pipelined drivers drain instead of issuing ahead so injected
        faults keep their serial-schedule ordering."""
        sup = self.supervisor
        return sup is not None and sup.pending_disruption

    # -- pipelined CPU↔TPU handoff (core/pipeline.py) --

    def _pipeline(self):
        """The two-slot pipeline for one driver-loop invocation, or None
        when the serial arm is configured. Stats accumulate across loops
        on the same sim (the dict is shared)."""
        if not self.pipelined_dispatch:
            return None
        if self._pipeline_stats is None:
            self._pipeline_stats = pipeline_mod.new_stats()
        return pipeline_mod.TwoSlotPipeline(self._pipeline_stats)

    def pipeline_stats(self) -> dict:
        """Pipelined-handoff telemetry for the metrics `pipeline.*`
        namespace (schema v14); {} until a pipelined driver loop ran
        (serial runs emit no pipeline keys)."""
        st = self._pipeline_stats
        return dict(st) if st is not None else {}

    def add_handoff_hook(self, fn, sharded: bool = False) -> None:
        """Register per-handoff host work, called inside every driver's
        host-drain phase (after the fault/checkpoint tick) — the
        managed-plane syscall-drain analog — which the pipelined loop
        overlaps with the in-flight dispatch. Hooks must not assume the
        next dispatch has not been issued; state mutations they make are
        detected and discard any in-flight speculation (the recompute
        rule).

        sharded=False: fn(sim, frontier_ns), one whole-sim call, always
        on the coordinator. sharded=True: fn(sim, frontier_ns, gid), one
        call per live host, partitioned by owning host across the
        multi-worker host plane (core/hostplane.py) — the call must only
        touch that host's partition-local state. With host_workers == 1
        sharded hooks run inline in the same canonical (frontier, gid)
        order the parallel merge uses, so both paths are bit-exact."""
        self._handoff_hooks.append((fn, bool(sharded)))

    # -- PARSIR-style multi-worker host plane (core/hostplane.py) --

    def _hostplane(self):
        """The drain-worker pool, or None on the serial path (host_workers
        == 1). Stats are created lazily so serial runs emit no
        hostplane.* keys."""
        if self.host_workers <= 1:
            return None
        if self._hostplane_obj is None:
            if self._hostplane_stats is None:
                self._hostplane_stats = hostplane_mod.new_stats(
                    self.host_workers
                )
            self._hostplane_obj = hostplane_mod.HostPlane(
                self.host_workers, self._hostplane_stats
            )
        return self._hostplane_obj

    def hostplane_stats(self) -> dict:
        """Host-plane telemetry for the metrics `hostplane.*` namespace
        (schema v15); {} until a multi-worker drain ran (host_workers ==
        1 emits no hostplane keys)."""
        st = self._hostplane_stats
        return dict(st) if st is not None else {}

    def _hostplane_slot_map(self):
        """The placement seam's host->slot table for worker pinning, read
        once per layout epoch (islands bump `rebalances` on every
        migration/relayout, which invalidates the cache — so a moved host
        re-pins deterministically). None = identity pinning."""
        slot = getattr(self.params, "slot_of", None)
        if slot is None:
            return None
        epoch = int(getattr(self, "rebalances", 0))
        cached = self._hostplane_slot_cache
        if cached is not None and cached[0] == epoch:
            return cached[1]
        m = np.asarray(jax.device_get(slot)).reshape(-1)
        self._hostplane_slot_cache = (epoch, m)
        return m

    def _run_handoff_hooks(self, mn: int) -> None:
        if not self._handoff_hooks:
            return
        sharded = [fn for fn, sh in self._handoff_hooks if sh]
        if sharded:
            hp = self._hostplane()
            if hp is None:
                # serial path: inline, in the same canonical (frontier,
                # gid, registration) order the parallel merge produces
                for gid in range(self.num_hosts):
                    for fn in sharded:
                        fn(self, mn, gid)
            else:
                hp.set_slot_map(self._hostplane_slot_map())
                obs = self.obs_session
                hp.drain(
                    [
                        hostplane_mod.HostAction(
                            mn, gid, (lambda f=fn, g=gid: f(self, mn, g))
                        )
                        for gid in range(self.num_hosts)
                        for fn in sharded
                    ],
                    tracer=obs.tracer if obs is not None else None,
                )
        for fn, sh in self._handoff_hooks:
            if not sh:
                fn(self, mn)

    def _handoff_quiet(self, mn: int) -> bool:
        """True when the upcoming handoff tick at committed frontier
        `mn` cannot mutate state: no due injection or checkpoint mark at
        or below the frontier, no quarantined-host recurring drain, no
        forced/sustained spill episode. The pipelined drivers only issue
        ahead across QUIET boundaries — state-mutating ticks are barrier
        points (docs/architecture.md §Pipelined handoff)."""
        if self._dead_hosts or self._force_spill:
            return False
        pc = self.pressure
        if (pc is not None and pc.saturate_frac is not None
                and pc.saturate_frac < 1.0):
            return False
        if self._fault_plane_active() and self._fault_mark() <= mn:
            return False
        return True

    def _rebind_kernels(self) -> None:
        """Drop every compiled kernel and rebind the active gear — the
        hot-resume step after a backend returns (stale executables point
        at the dead client) and the re-lowering step entering/leaving CPU
        failover. The optimistic attempt kernel is re-ensured when a
        lazily-compiling engine (islands) had one bound."""
        had_attempt = getattr(self, "_attempt", None) is not None
        self._gear_fns = {}
        self._bind_gear()
        ensure = getattr(self, "_ensure_optimistic", None)
        if had_attempt and self._attempt is None and ensure is not None:
            ensure()

    def _enter_cpu_failover(self) -> None:
        """Degraded-mode failover: move state/params to the CPU backend
        and re-lower the window kernels there. The simulation keeps
        advancing (slower); results are bit-identical — the kernels are
        pure integer programs, and the audit chain proves it."""
        if getattr(self, "mode", None) == "shard_map":
            raise RuntimeError(
                "CPU failover is not available under shard_map islands "
                "(the mesh IS the lost device set); use --on-backend-loss "
                "wait or abort"
            )
        try:
            dev = jax.devices("cpu")[0]
        except RuntimeError as e:
            raise RuntimeError(f"no CPU backend to fail over to: {e}") from e
        self.state = jax.device_put(jax.device_get(self.state), dev)
        self.params = jax.device_put(jax.device_get(self.params), dev)
        self._cpu_failover = True
        self._rebind_kernels()

    def _exit_cpu_failover(self) -> None:
        """Upshift back to the recovered primary backend: move state home
        and rebind the primary kernels."""
        self._cpu_failover = False
        self.state = jax.device_put(jax.device_get(self.state))
        self.params = jax.device_put(jax.device_get(self.params))
        self._rebind_kernels()

    def _drain_to_checkpoint(self, reason: str,
                             ckpt_dir: str | None = None) -> str | None:
        """Flush the committed frontier to a crash-consistent ring
        checkpoint with drain-reason metadata (the supervisor's first act
        on backend loss). `self.state` at a dispatch boundary is the last
        committed pytree — the failed dispatch never assigned — so the
        drain is exactly the crash-consistent checkpoint path, audit
        chain included. Returns the path, or None when no checkpoint
        directory is configured (in-memory recovery still proceeds)."""
        from shadow_tpu.core import checkpoint as ckpt_mod

        d = ckpt_dir or self.checkpoint_dir
        if not d:
            return None
        mn = int(np.min(np.asarray(jax.device_get(self.state.pool.time))))
        t = max(0, min(mn, self.stop_time))
        sup = self.supervisor
        # drains live in their own `drain-*` ring namespace: a burst of
        # backend/chip losses rotates drains against drains only, never
        # the periodic ring (core/checkpoint.save_ring prefix rule)
        path, pruned = ckpt_mod.save_ring(
            self, d, self._ckpt_seq, t, self.checkpoint_retain,
            extra_meta={"drain": {
                "reason": reason,
                "policy": sup.policy if sup is not None else "abort",
                "frontier_ns": t,
            }},
            prefix="drain",
        )
        self._ckpt_seq += 1
        self.fault_counters["checkpoints_written"] += 1
        self.fault_counters["checkpoints_pruned"] += pruned
        obs = self.obs_session
        if obs is not None and obs.tracer:
            obs.tracer.fault("drain_checkpoint", sim_ns=t, reason=reason)
        return path

    def resilience_stats(self) -> dict:
        """Supervisor telemetry for the metrics `resilience.*` namespace
        (schema v6); {} when no supervisor is attached."""
        sup = self.supervisor
        return sup.stats() if sup is not None else {}

    # -- resource-pressure plane (core/pressure.py) --

    def attach_pressure(self, controller) -> None:
        """Arm a custom pressure controller/policy; the drivers attach
        the default ladder lazily on the first pressure signal."""
        self.pressure = controller

    def _pressure(self):
        if self.pressure is None:
            self.pressure = pressure_mod.PressureController()
        return self.pressure

    def _pressure_ladder_step(self, label: str) -> bool:
        """One memory-ladder rung for a classified RESOURCE_EXHAUSTED
        dispatch failure (called by the supervisor between attempts)."""
        return self._pressure().on_backend_exhausted(self, label)

    def _pressure_stall(self, *, window=None, occupancy=None,
                        capacity=None) -> bool:
        """One pool-ladder consultation at a driver stall; True = retry
        the driver loop (a rung reshaped something)."""
        return self._pressure().on_pool_exhausted(
            self, window=window, occupancy=occupancy, capacity=capacity
        )

    def _pool_exhausted(self, message: str, window=None,
                        occupancy=None, capacity=None):
        """Terminal pool exhaustion: drain the committed frontier to the
        checkpoint ring (when one is configured — the run is resumable at
        a reshaped config, docs/fault_tolerance.md §5) and build the
        typed error every driver raises instead of a bare RuntimeError."""
        path = self._drain_to_checkpoint("pool_exhausted")
        if path:
            message += f" (drained to {path}; resume with --resume)"
        return pressure_mod.PoolExhausted(
            message, window=window, occupancy=occupancy, capacity=capacity
        )

    def _pressure_relieve_pool(self, step: int):
        """The pool-exhaustion rungs, in ladder order. Returns the action
        name or None when exhausted (core/pressure.py counts them)."""
        pc = self._pressure()
        pol = pc.policy
        # rung 1: forced upshift — more usable pool, unless a memory hold
        # pins the gear down or no bigger gear exists
        if (self._shifter is not None and not pc.hold_gear
                and self._gear < self._gear_ladder[-1].level):
            self._shift_gear(self._gear + 1)
            return "upshift"
        # rung 2 (saturation yield) lives in the controller
        # rung 3: force one spill episode — the stall may predate any
        # red-zone crossing (occupancy under the mark can still leave too
        # little merge headroom for a whole window's inflow)
        if pol.allow_spill_escalation and not self._force_spill \
                and step < 1 + pol.max_fill_shrink:
            self._force_spill = True
            return "spill_escalation"
        return None

    def _pressure_relieve_memory(self, step: int):
        """The memory-exhaustion rungs, in ladder order: forced gear
        downshift (red-zone rule overridden), then spill-fill escalation.
        The fleet adds lane eviction; the supervisor's drain + policy is
        the rung after None."""
        pc = self._pressure()
        pol = pc.policy
        if (pol.allow_downshift and self._pressure_reshape_ok
                and len(self._gear_ladder) > 1
                and self._gear > self._gear_ladder[0].level
                and self._pressure_downshift()):
            pc.hold_gear = True
            return "downshift"
        if pol.allow_spill_escalation and pc.fill_shrink < pol.max_fill_shrink:
            pc.fill_shrink += 1
            self._force_spill = True
            return "spill_escalation"
        return None

    def _pressure_downshift(self) -> bool:
        """Forced downshift one gear under memory pressure: park rows
        beyond the TARGET gear's fill mark on the host spill tier (one
        manage pass — foreign-row re-routing and whole-host ordering
        included), then re-sort the pool into the smaller capacity. The
        resize drops nothing (occupancy <= fill < capacity after the
        park), so results stay bit-identical — the spill tier's
        guarantee."""
        target = self._gear - 1
        spec = self._gear_ladder[target]
        spill = self._spill_store()
        self._force_spill = True
        self._pressure_fill_cap = max(1, min(spec.fill, spec.hi))
        try:
            spill_mod.manage(self, spill, self.stop_time)
        finally:
            self._pressure_fill_cap = None
        self._shift_gear(target)
        return True

    def pressure_stats(self) -> dict:
        """Pressure-plane telemetry for the metrics `pressure.*`
        namespace (schema v8); {} until a pressure signal engaged."""
        pc = self.pressure
        return pc.stats() if pc is not None else {}

    def configure_auto_checkpoint(
        self, ckpt_dir: str, every_ns: int, retain: int = 3
    ) -> None:
        """Arm crash-consistent ring checkpoints every `every_ns` of sim
        time, written at handoff boundaries (core/checkpoint.save_ring).
        Safe to call after resume: ring numbering continues past existing
        entries and the next boundary is derived from the restored clock."""
        from shadow_tpu.core import checkpoint as ckpt_mod

        self.checkpoint_dir = str(ckpt_dir)
        self.checkpoint_every_ns = int(every_ns)
        self.checkpoint_retain = max(1, int(retain))
        now = int(np.max(np.asarray(jax.device_get(self.state.now))))
        if self.checkpoint_every_ns > 0:
            self._ckpt_next_t = (
                (now // self.checkpoint_every_ns) + 1
            ) * self.checkpoint_every_ns
        entries = ckpt_mod.ring_entries(self.checkpoint_dir)
        self._ckpt_seq = entries[-1][0] + 1 if entries else 0

    def resume_from(self, ckpt_dir: str) -> dict:
        """Restore the newest checkpoint in `ckpt_dir` that passes
        integrity validation, falling back past corrupt entries."""
        from shadow_tpu.core import checkpoint as ckpt_mod

        info = ckpt_mod.resume_latest(self, ckpt_dir)
        self.fault_counters["resume_fallbacks"] += info["fallbacks"]
        # Backend injections at or before the restored frontier already
        # happened — the outage was the very reason this run is resuming.
        # Marking them fired stops a re-attached plan from re-draining the
        # resumed run the moment it dispatches. skew_hosts joins them:
        # its effect (the replicated pool rows) is IN the restored state,
        # so re-firing would double-inject and diverge from the
        # uninterrupted chain. kill_host deliberately stays re-fireable —
        # quarantine is idempotent, and re-firing rebuilds the dead-host
        # set (runtime state no checkpoint carries).
        inj = self.fault_injector
        if inj is not None:
            from shadow_tpu.faults import plan as plan_mod

            replayed = plan_mod.BACKEND_OPS | {"skew_hosts"}
            for f in inj.faults:
                if (not f.fired and f.op in replayed
                        and f.at_ns <= info["sim_ns"]):
                    inj.mark_fired(f)
        return info

    def _resolve_host_id(self, host) -> int:
        if isinstance(host, (int, np.integer)):
            hid = int(host)
        else:
            cfg = getattr(self, "config", None)
            names = [h.name for h in cfg.hosts] if cfg is not None else []
            if host not in names:
                raise ValueError(
                    f"kill_host: unknown host {host!r} (named lookup needs "
                    f"a config-built sim; known: {names[:8]})"
                )
            hid = names.index(host)
        if not 0 <= hid < self.num_hosts:
            raise ValueError(
                f"kill_host: host id {hid} out of range [0, {self.num_hosts})"
            )
        return hid

    def quarantine_host(self, host) -> int:
        """Mark a simulated host dead (crashed-host semantic): its pending
        device-plane events are drained now and at every subsequent
        handoff — exchange-deferred rows that arrive later are caught by
        the recurring drain, which is what makes quarantine compose with
        the islands shard exchange. Events it already emitted remain in
        flight (a crashed host's packets still arrive). Idempotent;
        returns rows drained by this call."""
        hid = self._resolve_host_id(host)
        if hid in self._dead_hosts:
            return 0
        self._dead_hosts.add(hid)
        self.fault_counters["hosts_quarantined"] += 1
        obs = self.obs_session
        if obs is not None and obs.tracer:
            obs.tracer.fault("quarantine_host", host=hid)
        return self._drain_dead()

    def _drain_dead(self) -> int:
        """Cancel pool + spill rows destined to quarantined hosts. Runs at
        handoff boundaries only (the pool is about to be re-sorted by the
        next window's merge; a freed NEVER row is just a free slot)."""
        pool = self.state.pool
        dead = jnp.asarray(sorted(self._dead_hosts), pool.dst.dtype)
        mask = jnp.isin(pool.dst, dead) & (pool.time != NEVER)
        n = int(jnp.sum(mask))
        if n:
            self.state = self.state.replace(
                pool=pool.replace(time=jnp.where(mask, NEVER, pool.time))
            )
        sp = getattr(self, "_spill", None)
        if sp is not None:
            n += sp.drain_hosts(self._dead_hosts)
        if n:
            self.fault_counters["events_drained"] += n
            self.state = obs_mod.bump_win(self.state, obs_mod.WIN_FAULTS)
        return n

    def skew_hosts(self, hosts, factor: int) -> int:
        """Deterministic traffic-skew injection (the ``skew_hosts`` fault
        op): multiply the selected hosts' event rates by `factor` from
        this handoff boundary on, by replicating each host's pending pool
        rows `factor - 1` times (copies one nanosecond apart — a strict
        total order on every engine layout; faults/injector.skew_pool_np).
        Runs at handoff boundaries only, where the dispatch clamp
        (_fault_mark) has pinned every frontier — including the async
        islands per-shard frontiers — at or below the injection time, so
        a copy (which inherits a pending event's time, at or after its
        owner shard's frontier) can never violate causality. Copies that
        do not fit the pool park on the spill tier (late, never lost).
        Quarantined hosts are skipped. Returns rows injected."""
        from shadow_tpu.faults import injector as inj_mod

        if factor < 2:
            return 0
        ids = [self._resolve_host_id(h) for h in hosts]
        pool = self.state.pool
        cols = [
            np.array(jax.device_get(c)) for c in (
                pool.time, pool.dst, pool.src, pool.seq, pool.kind,
                pool.payload,
            )
        ]
        flat = cols[0].ndim == 1  # global [C] layout vs islands [S, C]
        if flat:
            cols = [c[None] for c in cols]
        (t, d, s, q, k, p), made, overflow = inj_mod.skew_pool_np(
            cols, ids, factor, dead=self._dead_hosts
        )
        parked = 0
        if overflow:
            sp = self._spill_store()
            for r, rows in sorted(overflow.items()):
                parked += sp.park(r, rows)
            self._force_spill = True  # manage() re-admits parked rows
        if flat:
            t, d, s, q, k, p = (c[0] for c in (t, d, s, q, k, p))
        self.state = self.state.replace(pool=pool.replace(
            time=jnp.asarray(t), dst=jnp.asarray(d), src=jnp.asarray(s),
            seq=jnp.asarray(q), kind=jnp.asarray(k),
            payload=jnp.asarray(p),
        ))
        self.fault_counters["events_skewed"] = (
            self.fault_counters.get("events_skewed", 0) + made + parked
        )
        self.state = obs_mod.bump_win(self.state, obs_mod.WIN_FAULTS)
        obs = self.obs_session
        if obs is not None and obs.tracer:
            obs.tracer.fault(
                "skew_hosts", hosts=len(ids), factor=int(factor),
                injected=made + parked,
            )
        return made + parked

    def _skew_fault_ids(self, f) -> list:
        """Resolve a skew_hosts fault's host selection (id/name list or
        [first, count] span) against this sim's host table."""
        if f.span is not None:
            first, count = f.span
            if first >= self.num_hosts:
                raise ValueError(
                    f"skew_hosts: span start {first} out of range "
                    f"[0, {self.num_hosts})"
                )
            return list(range(first, min(first + count, self.num_hosts)))
        return list(f.hosts or [])

    def _handoff_tick(self, mn: int) -> None:
        """The fault-plane + auto-checkpoint hook every driver calls at
        its handoff boundary (state synced, `mn` = committed frontier):
        fire due device/file injections, drain quarantined hosts' events,
        and write a ring checkpoint when the frontier crosses the next
        checkpoint mark. Zero work — four attribute checks — when neither
        faults nor checkpointing are configured."""
        inj = self.fault_injector
        obs = self.obs_session
        drained_this_tick = False
        if inj is not None and inj.pending:
            from shadow_tpu.faults import injector as inj_mod
            from shadow_tpu.faults import plan as plan_mod

            for f in inj.due(mn, plan_mod.DEVICE_OPS | plan_mod.FILE_OPS):
                if f.op == "kill_host":
                    self.quarantine_host(f.host)
                    drained_this_tick = True
                elif f.op == "skew_hosts":
                    self.skew_hosts(self._skew_fault_ids(f), f.factor)
                elif f.op == "force_spill":
                    self._force_spill = True
                    self.state = obs_mod.bump_win(
                        self.state, obs_mod.WIN_FAULTS
                    )
                elif f.op == "saturate_pool":
                    # injected pool saturation (core/pressure.py): scale
                    # the spill marks by frac from this frontier on; the
                    # sustained re-force below keeps the episodes coming
                    self._pressure().saturate(f.frac)
                    self._force_spill = True
                    self.state = obs_mod.bump_win(
                        self.state, obs_mod.WIN_FAULTS
                    )
                elif f.op == "corrupt_file":
                    touched = inj_mod.corrupt_file(
                        f, default_dir=self.checkpoint_dir
                    )
                    self.fault_counters["files_corrupted"] += len(touched)
                    self.state = obs_mod.bump_win(
                        self.state, obs_mod.WIN_FAULTS
                    )
                else:
                    # every DEVICE/FILE op must carry an explicit arm —
                    # the contract auditor (analysis/contracts.py SLC003)
                    # checks each registered op is named here, so a new
                    # plan op cannot silently fall through
                    raise RuntimeError(
                        f"fault op {f.op!r} has no device-plane handler"
                    )
                if obs is not None and obs.tracer:
                    obs.tracer.fault(
                        "fault_injection", op=f.op, at_ns=f.at_ns
                    )
            for f in inj.due(mn, plan_mod.BACKEND_OPS):
                # backend ops drive the supervisor's state machine; the
                # NEXT supervised dispatch sees the simulated loss/stall
                sup = self.supervisor
                if sup is None:
                    from shadow_tpu.core.supervisor import BackendSupervisor

                    sup = BackendSupervisor()
                    self.attach_supervisor(sup)
                if f.op == "kill_backend":
                    sup.inject_kill(f.recover_after)
                elif f.op == "kill_chip":
                    sup.inject_kill_chip(f.chip, f.recover_after)
                elif f.op == "exhaust_backend":
                    sup.inject_exhaust(f.recover_after)
                elif f.op == "stall_backend":
                    sup.inject_stall(f.count)
                else:
                    # explicit arms only (contracts.py SLC003, as above)
                    raise RuntimeError(
                        f"fault op {f.op!r} has no backend handler"
                    )
                if obs is not None and obs.tracer:
                    obs.tracer.fault(
                        "fault_injection", op=f.op, at_ns=f.at_ns
                    )
        if (self.pressure is not None
                and self.pressure.saturate_frac is not None
                and self.pressure.saturate_frac < 1.0):
            # sustained saturation: keep the spill tier engaged so the
            # scaled marks keep parking rows every handoff
            self._force_spill = True
        if self._dead_hosts and not drained_this_tick:
            # recurring drain: exchange-deferred / late-emitted rows for
            # dead hosts are cancelled before the next window runs
            self._drain_dead()
        if self.checkpoint_every_ns and mn >= self._ckpt_next_t:
            from shadow_tpu.core import checkpoint as ckpt_mod

            t = min(int(mn), self.stop_time)
            with metrics_mod.span(obs, "checkpoint"):
                path, pruned = ckpt_mod.save_ring(
                    self, self.checkpoint_dir, self._ckpt_seq, t,
                    self.checkpoint_retain,
                )
            self._ckpt_seq += 1
            self.fault_counters["checkpoints_written"] += 1
            self.fault_counters["checkpoints_pruned"] += pruned
            if obs is not None and obs.tracer:
                obs.tracer.fault("checkpoint", sim_ns=t)
            self._ckpt_next_t = (
                (t // self.checkpoint_every_ns) + 1
            ) * self.checkpoint_every_ns

    def _fault_plane_active(self) -> bool:
        """True when a handoff tick has work to do — the drivers skip the
        tick (and any re-sync it would force) entirely otherwise."""
        return (
            self.fault_injector is not None
            or bool(self._dead_hosts)
            or bool(self.checkpoint_every_ns)
        )

    def _fault_mark(self) -> int:
        """Earliest virtual time the fused drivers must create a handoff
        boundary at: the next unfired device/file injection or the next
        checkpoint mark. Multi-window dispatches clamp their stop time
        here — otherwise a 64-window dispatch would sail seconds past a
        scheduled injection and both the checkpoint cadence and the fault
        plan's timing would degrade to dispatch granularity."""
        mark = int(NEVER)
        inj = self.fault_injector
        if inj is not None:
            from shadow_tpu.faults import plan as plan_mod

            ops = (
                plan_mod.DEVICE_OPS | plan_mod.FILE_OPS
                | plan_mod.BACKEND_OPS
            )
            for f in inj.faults:
                if not f.fired and f.op in ops:
                    mark = min(mark, f.at_ns)
        if self.checkpoint_every_ns:
            mark = min(mark, self._ckpt_next_t)
        return mark

    def fault_stats(self) -> dict:
        """Fault-plane telemetry for metrics dumps (faults.* namespace,
        schema v3) and bench rows."""
        d = dict(self.fault_counters)
        if self.fault_injector is not None:
            d.update(self.fault_injector.stats())
        return d

    def counters(self) -> dict[str, int]:
        return _counters_dict(jax.device_get(self.state.counters))

    def obs_snapshot(self) -> dict:
        """The device telemetry block (obs/counters.py), normalized across
        engine layouts; {} when built with obs_counters=False. Read at
        handoff boundaries only — it device_gets the block."""
        return obs_mod.snapshot(self.state)

    # -- determinism audit plane (obs/audit.py, obs/flight.py) --

    def attach_audit(self, meta: dict | None = None):
        """Arm per-handoff digest-chain recording (--digest-out). Needs
        the obs block (the chain lives in it)."""
        if self.state.obs is None:
            raise ValueError(
                "digest auditing needs the obs block "
                "(experimental.obs_counters: true)"
            )
        self.audit = audit_mod.AuditTrail(meta)
        return self.audit

    def attach_flight_spool(self, path: str):
        """Arm flight-ring spooling to `path` (--flight-out). Needs the
        ring compiled in (experimental.flight_recorder)."""
        if self.state.flight is None:
            raise ValueError(
                "flight spooling needs experimental.flight_recorder "
                "(the ring compiles into the kernel)"
            )
        self.flight_spool = flight_mod.FlightSpool(
            path, self.num_hosts, self.state.flight.capacity
        )
        return self.flight_spool

    def audit_chain(self) -> int:
        """The current global digest-chain value: one obs-block fetch plus
        the order-independent per-host combine. 0 when the block is off."""
        snap = self.obs_snapshot()
        if not snap or "host_digest" not in snap:
            return 0
        return audit_mod.combine(snap["host_digest"])

    def write_digest(self, path: str) -> dict:
        """Dump the digest document (--digest-out): chain records, final
        per-host sub-chains, final combined chain."""
        if self.audit is None:
            raise ValueError("no audit trail attached (attach_audit first)")
        return self.audit.dump(path, self.obs_snapshot())

    def _audit_active(self) -> bool:
        return self.audit is not None or self.flight_spool is not None

    def _audit_tick(self, mn: int) -> None:
        """Record the digest chain and flush the flight ring at a handoff
        boundary the driver already synced at. Zero work unless a trail
        or spool is attached."""
        if not self._audit_active():
            return
        frontier = min(int(mn), self.stop_time)
        if self.audit is not None:
            snap = self.obs_snapshot()
            if snap:
                self.audit.record(snap, frontier)
        if self.flight_spool is not None:
            # per-host ring extraction shards across the host plane's
            # pinned workers (core/hostplane.py); bytes identical either
            # way (canonical-order merge + the sort below)
            self.flight_spool.flush(self, frontier,
                                    plane=self._hostplane())

    def save_checkpoint(self, path: str) -> None:
        """Snapshot the full device state to disk (resume is bit-exact)."""
        from shadow_tpu.core import checkpoint

        checkpoint.save(self, path)

    def load_checkpoint(self, path: str) -> None:
        """Restore state saved by save_checkpoint; this Simulation must be
        built from the same config."""
        from shadow_tpu.core import checkpoint

        checkpoint.restore(self, path)

    def host_trackers(self) -> dict[str, "np.ndarray"]:
        """Per-host byte/packet counters from the device NIC state
        (tracker.c analog); empty if the sim has no network stack."""
        sub = self.state.subs.get("nic")
        if sub is None:
            return {}
        return {
            k: np.asarray(jax.device_get(getattr(sub, k)))
            for k in ("tx_packets", "tx_bytes", "rx_packets", "rx_bytes")
        }
