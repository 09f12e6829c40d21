"""Upstream-router queue with CoDel AQM, vectorized per host.

Reference (src/main/routing/router_queue_codel.c, RFC 8289): packets from
the simulated network enter the host's upstream-ISP router queue; the NIC
receive path dequeues them. CoDel tracks per-packet sojourn time; if it
stays ≥ TARGET (10 ms, Shadow's doubled value) for a full INTERVAL (100 ms),
the router enters drop mode and drops with increasing frequency per the
control law, until delays recover.

Differences from the reference, both deliberate:
- The reference's control law divides the absolute timestamp by sqrt(count)
  (`(ts + interval)/sqrt(count)`), which for count > 1 produces a
  next-drop time far in the past and collapses into consecutive drops. We
  implement the law its own comments cite (RFC 8289):
  next = ts + interval/sqrt(count).
- At most DROP_UNROLL packets are dropped per dequeue; a longer drop burst
  continues on the next pump round (the receive pump re-arms itself while
  the queue is non-empty), so bursts are spread over same-timestamp
  micro-steps instead of one call.
- The queue is a bounded ring (the reference is unbounded); overflow drops
  are counted separately.

Two rings. Every host has a slot in the shallow ring `[H, Q, P]`, whose
reads and writes are one-hot selects over the whole ring (soa.py). Hosts
that a host group gives their own `router_queue_slots` (the few a fan-in
loads) use a deep ring `[B, Qd, W]` over those B hosts instead: one row
per packet (payload words, source, enqueue time), written by a scatter
and read by a gather of B rows, so its cost follows B and not Qd. The
head, tail and CoDel state stay `[H]`; a deep host behaves exactly as it
would in a uniform ring of depth Qd.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from flax import struct

from shadow_tpu.core import simtime, soa
from shadow_tpu.core.state import PAYLOAD_WORDS
from shadow_tpu.net import packet as pkt

TARGET_NS = 10 * simtime.NS_PER_MS
INTERVAL_NS = 100 * simtime.NS_PER_MS
DROP_UNROLL = 1

SUB = "router"


@struct.dataclass
class RouterState:
    # ring [H, Q]
    q_payload: jnp.ndarray  # [H, Q, P] i32
    q_src: jnp.ndarray  # [H, Q] i32
    q_enq_ts: jnp.ndarray  # [H, Q] i64
    q_head: jnp.ndarray  # [H] i32
    q_tail: jnp.ndarray  # [H] i32
    # codel per-host state
    drop_mode: jnp.ndarray  # [H] bool (False = store)
    interval_expire: jnp.ndarray  # [H] i64 (0 = unset)
    next_drop: jnp.ndarray  # [H] i64
    drop_count: jnp.ndarray  # [H] i32
    drop_count_last: jnp.ndarray  # [H] i32
    total_size: jnp.ndarray  # [H] i64 queued wire bytes
    # counters
    codel_dropped: jnp.ndarray  # [] i64
    overflow_dropped: jnp.ndarray  # [] i64
    # last AQM-dropped packet per host (PDS breadcrumb registers; trail
    # word is 0 for simulations built without packet_trails)
    drop_trail: jnp.ndarray  # [H] i32
    drop_time: jnp.ndarray  # [H] i64
    # the deep ring (None when no host is deep): one row per packet,
    # payload words then source then enqueue time (low, high word)
    deep_rows: jnp.ndarray | None = None  # [B, Qd, P + 3] i32
    deep_host: jnp.ndarray | None = None  # [B] i32 host of each deep row
    deep_of: jnp.ndarray | None = None  # [H] i32 deep row, -1 = shallow
    # high-water occupancy: the largest q_tail - q_head a deep host reached
    deep_high_water: jnp.ndarray | None = None  # [] i32


def init(num_hosts: int, queue_slots: int = 64,
         payload_words: int = PAYLOAD_WORDS, deep_hosts=(),
         deep_slots: int = 0) -> RouterState:
    """`deep_hosts` (sorted host ids) get the deep ring of `deep_slots`
    slots each; every other host the shallow ring of `queue_slots`."""
    H, Q = num_hosts, queue_slots
    z64 = lambda: jnp.zeros((H,), jnp.int64)  # noqa: E731
    z32 = lambda: jnp.zeros((H,), jnp.int32)  # noqa: E731
    deep = {}
    if len(deep_hosts):
        B = len(deep_hosts)
        of = np.full(H, -1, np.int32)
        of[np.asarray(deep_hosts)] = np.arange(B, dtype=np.int32)
        deep = dict(
            deep_rows=jnp.zeros((B, deep_slots, payload_words + 3),
                                jnp.int32),
            deep_host=jnp.asarray(deep_hosts, jnp.int32),
            deep_of=jnp.asarray(of),
            deep_high_water=jnp.zeros((), jnp.int32),
        )
    return RouterState(
        q_payload=jnp.zeros((H, Q, payload_words), jnp.int32),
        q_src=jnp.zeros((H, Q), jnp.int32),
        q_enq_ts=jnp.zeros((H, Q), jnp.int64),
        q_head=z32(),
        q_tail=z32(),
        drop_mode=jnp.zeros((H,), bool),
        interval_expire=z64(),
        next_drop=z64(),
        drop_count=z32(),
        drop_count_last=z32(),
        total_size=z64(),
        codel_dropped=jnp.zeros((), jnp.int64),
        overflow_dropped=jnp.zeros((), jnp.int64),
        drop_trail=z32(),
        drop_time=z64(),
        **deep,
    )


def has_room(router: RouterState):
    """[H] bool: the host's ring, shallow or deep, has a free slot."""
    cap = router.q_src.shape[1]
    if router.deep_rows is not None:
        cap = jnp.where(router.deep_of >= 0, router.deep_rows.shape[1], cap)
    return (router.q_tail - router.q_head) < cap


def _deep_write(router: RouterState, ok, payload, src, now) -> RouterState:
    """Append one row per deep host where `ok`: a scatter of B rows (rows
    not written aim past the ring and are dropped)."""
    rows, Qd, _ = router.deep_rows.shape
    b = router.deep_host
    slot = jnp.where(ok[b], router.q_tail[b] % Qd, Qd)
    t = now[b]
    row = jnp.concatenate([
        payload[b], src[b][:, None].astype(jnp.int32),
        (t & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32)[:, None],
        (t >> 32).astype(jnp.int32)[:, None],
    ], axis=1)
    deep_rows = router.deep_rows.at[jnp.arange(rows), slot].set(
        row, mode="drop")
    return router.replace(deep_rows=deep_rows)


def _deep_head(router: RouterState, payload, src, enq_ts):
    """The head packet of each deep host (a gather of B rows), put in the
    deep hosts' places of the shallow ring's [H] reads."""
    rows, Qd, W = router.deep_rows.shape
    P = W - 3
    b = router.deep_host
    row = router.deep_rows[jnp.arange(rows), router.q_head[b] % Qd]
    # [B] -> [H]: a one-hot select over the B deep rows
    hit = router.deep_of[:, None] == jnp.arange(rows, dtype=jnp.int32)
    at = jnp.sum(jnp.where(hit[:, :, None], row[None], 0), axis=1,
                 dtype=jnp.int32)
    deep = (router.deep_of >= 0)
    ts = (at[:, P + 2].astype(jnp.int64) << 32) | (
        at[:, P + 1].astype(jnp.int64) & 0xFFFFFFFF)
    return (jnp.where(deep[:, None], at[:, :P], payload),
            jnp.where(deep, at[:, P], src),
            jnp.where(deep, ts, enq_ts))


def enqueue(router: RouterState, mask, payload, src, now) -> RouterState:
    """router_enqueue (router.c:103-121): append with enqueue timestamp."""
    H, Q = router.q_src.shape
    room = has_room(router)
    ok = mask & room
    now = jnp.broadcast_to(now, (H,)).astype(jnp.int64)
    src = src.astype(jnp.int32)
    shallow = ok if router.deep_rows is None else ok & (router.deep_of < 0)
    slot = router.q_tail % Q
    size = pkt.total_bytes(payload).astype(jnp.int64)
    if router.deep_rows is not None:
        router = _deep_write(router, ok, payload, src, now)
    router = router.replace(
        q_payload=soa.set_at(router.q_payload, shallow, slot, payload),
        q_src=soa.set_at(router.q_src, shallow, slot, src),
        q_enq_ts=soa.set_at(router.q_enq_ts, shallow, slot, now),
        q_tail=router.q_tail + ok.astype(jnp.int32),
        total_size=router.total_size + jnp.where(ok, size, 0),
        overflow_dropped=router.overflow_dropped
        + jnp.sum(mask & ~room, dtype=jnp.int64),
    )
    if router.deep_rows is not None:
        b = router.deep_host
        router = router.replace(deep_high_water=jnp.maximum(
            router.deep_high_water,
            jnp.max(router.q_tail[b] - router.q_head[b])))
    return router


def _record_drop(router: RouterState, mask, payload, now):
    """Keep the dropped (in-hand) packet's breadcrumb trail + drop time in
    per-host registers (packet.c PDS_* trail analog for the AQM's drops —
    they happen inside the dequeue walk where no caller sees the packet).
    Trail word 0 when the sim runs without packet_trails."""
    if payload.shape[-1] <= pkt.W_TRAIL:
        return router
    tr = (payload[..., pkt.W_TRAIL] << 4) | jnp.int32(pkt.PDS_DROPPED_CODEL)
    return router.replace(
        drop_trail=jnp.where(mask, tr, router.drop_trail),
        drop_time=jnp.where(
            mask, jnp.broadcast_to(now, mask.shape).astype(jnp.int64),
            router.drop_time,
        ),
    )


def _control_law(count, ts):
    # next = ts + interval/sqrt(count); float64 is fine here — this runs on
    # [H] scalars a few times per dequeue, not in the packet fast path.
    inc = jnp.round(
        INTERVAL_NS / jnp.sqrt(jnp.maximum(count, 1).astype(jnp.float64))
    ).astype(jnp.int64)
    return ts + inc


def _pop_helper(router: RouterState, now, want):
    """One masked ring pop with sojourn bookkeeping
    (_routerqueuecodel_dequeueHelper). Returns
    (router, have [H], payload [H,P], src [H], ok_to_drop [H])."""
    H, Q = router.q_src.shape
    hosts = jnp.arange(H, dtype=jnp.int32)
    nonempty = router.q_head < router.q_tail
    have = want & nonempty
    empty_hit = want & ~nonempty

    slot = router.q_head % Q
    # one-hot ring reads — row gathers serialize on TPU (soa.get_at)
    payload = soa.get_at(router.q_payload, slot)
    src = soa.get_at(router.q_src, slot)
    enq_ts = soa.get_at(router.q_enq_ts, slot)
    if router.deep_rows is not None:
        payload, src, enq_ts = _deep_head(router, payload, src, enq_ts)

    size = pkt.total_bytes(payload).astype(jnp.int64)
    new_total = jnp.where(have, router.total_size - size, router.total_size)
    sojourn = now - enq_ts
    good = (sojourn < TARGET_NS) | (new_total < pkt.MTU)

    # good state: reset interval expiration
    interval_expire = jnp.where(have & good, 0, router.interval_expire)
    # bad state, first time: arm the interval
    entering_bad = have & ~good & (router.interval_expire == 0)
    interval_expire = jnp.where(entering_bad, now + INTERVAL_NS, interval_expire)
    # bad state, sustained a full interval: ok to drop
    ok_to_drop = have & ~good & (router.interval_expire != 0) & (
        now >= router.interval_expire
    )
    # empty queue resets the interval expiration
    interval_expire = jnp.where(empty_hit, 0, interval_expire)

    router = router.replace(
        q_head=router.q_head + have.astype(jnp.int32),
        total_size=new_total,
        interval_expire=interval_expire,
    )
    return router, have, payload, src, ok_to_drop


def dequeue(router: RouterState, now, mask, aqm: bool = True):
    """CoDel dequeue (_routerqueuecodel_dequeue), one deliverable packet per
    masked host. Returns (router, have, payload, src).

    aqm=False gives the reference's non-AQM router variants
    (router_queue_static.c / router_queue_single.c): a plain drop-tail
    FIFO pop with no control law — "single" is this with a 1-slot ring.
    """
    if not aqm:
        router, have, payload, src, _ok = _pop_helper(router, now, mask)
        return router, have, payload, src
    router, have, payload, src, ok = _pop_helper(router, now, mask)

    # empty → store mode
    router = router.replace(
        drop_mode=jnp.where(mask & ~have, False, router.drop_mode)
    )

    in_drop = mask & have & router.drop_mode
    # delays low again → leave drop mode
    router = router.replace(
        drop_mode=jnp.where(in_drop & ~ok, False, router.drop_mode)
    )

    # drop-mode loop: drop while now >= next_drop (bounded unroll).
    # `ok` tracks the okToDrop verdict of the packet CURRENTLY in hand —
    # it must follow each re-pop or a fresh low-sojourn packet would be
    # judged by its dropped predecessor's verdict.
    for _ in range(DROP_UNROLL):
        cond = mask & have & router.drop_mode & (now >= router.next_drop)
        router = router.replace(
            codel_dropped=router.codel_dropped + jnp.sum(cond, dtype=jnp.int64),
            drop_count=router.drop_count + cond.astype(jnp.int32),
        )
        router = _record_drop(router, cond, payload, now)
        router, have2, payload2, src2, ok2 = _pop_helper(router, now, cond)
        have = jnp.where(cond, have2, have)
        payload = jnp.where(cond[:, None], payload2, payload)
        src = jnp.where(cond, src2, src)
        ok = jnp.where(cond, ok2, ok)
        router = router.replace(
            next_drop=jnp.where(
                cond & ok2,
                _control_law(router.drop_count, router.next_drop),
                router.next_drop,
            ),
            drop_mode=jnp.where(cond & ~ok2, False, router.drop_mode),
        )

    # store mode but the packet in hand should now drop: drop it, enter
    # drop mode
    trans = mask & have & ~router.drop_mode & ok
    router = router.replace(
        codel_dropped=router.codel_dropped + jnp.sum(trans, dtype=jnp.int64)
    )
    router = _record_drop(router, trans, payload, now)
    router, have3, payload3, src3, _ok3 = _pop_helper(router, now, trans)
    have = jnp.where(trans, have3, have)
    payload = jnp.where(trans[:, None], payload3, payload)
    src = jnp.where(trans, src3, src)
    delta = router.drop_count - router.drop_count_last
    recently = now < (router.next_drop + 16 * INTERVAL_NS)
    new_count = jnp.where(recently & (delta > 1), delta, 1).astype(jnp.int32)
    router = router.replace(
        drop_mode=jnp.where(trans, True, router.drop_mode),
        drop_count=jnp.where(trans, new_count, router.drop_count),
        next_drop=jnp.where(
            trans, _control_law(new_count, jnp.broadcast_to(now, new_count.shape)),
            router.next_drop,
        ),
        drop_count_last=jnp.where(trans, new_count, router.drop_count_last),
    )
    return router, have, payload, src


def nonempty(router: RouterState):
    return router.q_head < router.q_tail
