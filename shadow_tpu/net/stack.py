"""NetStack: composes NIC + router(CoDel) + UDP into engine handlers.

Wiring mirrors the reference's packet path (SURVEY.md §3.4):

  send:    app → udp_sendto → NIC send ring → send pump (tokens, qdisc)
           → link transit (loss roll + latency) → KIND_PKT_DELIVER event
  receive: KIND_PKT_DELIVER → router CoDel enqueue → receive pump
           (rx tokens) → CoDel dequeue → port demux → socket counters
           → app receive hooks

Loopback traffic (dst == src host) bypasses router and token buckets, like
the reference's loopback interface which has no upstream router
(network_interface.c:448-457).

Event kinds used: KIND_PKT_DELIVER, KIND_NIC_SEND (send pump),
KIND_NIC_REFILL is reused as the receive pump kind (KIND_NIC_RECV alias).

Inside the window kernel's `win.handlers`, three `jax.named_scope`s name
the layers (metadata only): `net.nic` (token buckets and the NIC send
ring), `net.router` (the router rings and CoDel), `net.udp` (socket
demux, delivery and counts).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from shadow_tpu.core.engine import Emitter, EventView
from shadow_tpu.core.state import (
    KIND_NIC_REFILL,
    KIND_PKT_DELIVER,
    NetParams,
    SimState,
)
from shadow_tpu.net import codel, link, nic, packet as pkt, pds as pds_mod, tcp as tcp_mod, udp
from shadow_tpu.net import qdisc as qdisc_mod

KIND_NIC_SEND = 100
KIND_NIC_RECV = KIND_NIC_REFILL

# hook(state, mask, slot, src_host, payload, emitter, now, params) -> state
RecvHook = Callable

NIC_SCOPE, ROUTER_SCOPE, UDP_SCOPE = "net.nic", "net.router", "net.udp"


class NetStack:
    def __init__(
        self,
        num_hosts: int,
        bw_up_bits,
        bw_down_bits,
        sockets_per_host: int = 8,
        router_queue_slots: int = 64,
        nic_queue_slots: int = 64,
        tcp_ooo_chunks: int = tcp_mod.OOO_CHUNKS,
        with_tcp: bool = True,
        tcp_child_base: int = 0,
        qdisc: str = "fifo",
        router_variant: str = "codel",
        payload_words: int = 12,
        discipline: qdisc_mod.Discipline | None = None,
        deep_hosts=(),
        deep_queue_slots: int = 0,
    ):
        # Egress scheduling plane: either a legacy string ("fifo" /
        # "roundrobin" — the ring-wrapping disciplines) or a prebuilt
        # Discipline object (pifo/eiffel, carrying rank/drop config from
        # the `qdisc:` section; sim.py constructs those).
        if discipline is None:
            discipline = qdisc_mod.make_discipline(qdisc)
        self.disc = discipline
        self.payload_words = payload_words
        if router_variant not in ("codel", "static", "single"):
            raise ValueError(f"unknown router variant {router_variant!r}")
        self.qdisc = discipline.name
        # router_queue_codel.c / _static.c / _single.c vtable analog:
        # "static" = drop-tail FIFO without the AQM control law;
        # "single" = the same with a one-packet ring
        self.router_aqm = router_variant == "codel"
        if router_variant == "single":
            router_queue_slots = 1
        self.sockets_per_host = sockets_per_host
        self.num_hosts = num_hosts
        self.disc.attach(self)
        self._init_nic = nic.init(
            bw_up_bits, bw_down_bits, nic_queue_slots,
            payload_words=payload_words,
        )
        # hosts a host group gives a deeper router ring share the deep ring
        self._init_router = codel.init(
            num_hosts, router_queue_slots, payload_words=payload_words,
            deep_hosts=deep_hosts, deep_slots=deep_queue_slots,
        )
        self._init_udp = udp.init(num_hosts, sockets_per_host)
        # UDP-only sims skip the TCP state machine entirely: its handlers
        # otherwise run (masked) every micro-step and dominate both compile
        # time and per-iteration cost.
        self.tcp = (
            tcp_mod.Tcp(num_hosts, sockets_per_host, tcp_ooo_chunks,
                        child_base=tcp_child_base,
                        payload_words=payload_words)
            if with_tcp else None
        )
        if self.tcp is not None:
            self.tcp.attach(self)
        self.recv_hooks: list[RecvHook] = []
        # Receive-pump batching unrolls _deliver_local (the full demux +
        # hooks + TCP suite) per drained packet; with TCP compiled in, the
        # unroll multiplies XLA compile time for little win, so batch only
        # the UDP-only build.
        self.recv_batch = 1 if with_tcp else self.PUMP_BATCH
        # Gated arrival batching (engine bulk): how many CONSECUTIVE
        # KIND_PKT_DELIVER events one host may consume per micro-step when
        # bulk_gate proves them all direct-deliverable. The reference
        # drains a whole arrival burst in ONE receivePackets task
        # (network_interface.c:448-485); this is that, vectorized. TCP
        # builds keep 1 for now: the segment handler arms sub-window RTO
        # timers, which the gate cannot bound statically.
        self.deliver_batch = 1 if with_tcp else 8

    # ---- build-time API ----

    def bind_udp(self, host, slot: int, port: int, peer_host: int = udp.ANY_PEER,
                 peer_port: int = 0):
        """Bind `slot` on one host, or on an array of hosts alike."""
        self._init_udp = udp.bind_static(
            self._init_udp, host, slot, port, peer_host, peer_port
        )

    def tcp_listen(self, host: int, slot: int, port: int):
        if self.tcp is None:
            raise ValueError("stack built with with_tcp=False")
        self.tcp.listen(host, slot, port)

    def on_receive(self, hook: RecvHook):
        self.recv_hooks.append(hook)

    def init_subs(self) -> dict:
        subs = {
            nic.SUB: self._init_nic,
            codel.SUB: self._init_router,
            udp.SUB: self._init_udp,
        }
        if self.tcp is not None:
            subs[tcp_mod.SUB] = self.tcp.init_sub()
        subs.update(self.disc.init_subs())
        return subs

    # ---- generic transmit path (all protocols) ----

    def _tx(self, state: SimState, emitter: Emitter, mask, now, dst_host,
            payload, params: NetParams | None = None):
        """Transmit an assembled packet (networkinterface_wantsSend analog).

        Uncontended fast path (requires ``params`` for the latency/loss
        lookup): empty send queue + tokens in the bucket → the packet goes
        onto the wire inside THIS micro-step, exactly like the reference's
        send loop which transmits immediately when tokens allow
        (network_interface.c:633-661) — the pump event exists only for the
        throttled/queued case. This halves the per-packet event chain.

        Returns (state, ok) where ok marks hosts whose packet was admitted.
        """
        hosts = state.host.gid  # GLOBAL ids of this shard's rows
        H = hosts.shape[0]
        n = state.subs[nic.SUB]
        now64 = jnp.broadcast_to(now, (H,)).astype(jnp.int64)
        direct = jnp.zeros((H,), bool)
        if params is not None:
            with jax.named_scope(NIC_SCOPE):
                # empty-queue test BEFORE any mutation: the refill touches
                # only the token bucket, never the queue plane
                queued_any = self.disc.nonempty(state)
                tx_rem, tx_tick = nic.lazy_refill(
                    n.tx_rem, n.tx_tick, n.tx_refill, n.tx_cap, now64, mask
                )
                n = n.replace(tx_rem=tx_rem, tx_tick=tx_tick)
                size = pkt.total_bytes(payload).astype(jnp.int64)
                bootstrap = now64 < params.bootstrap_end
                # same admission gate as the send pump (rem >= MTU, full
                # size charged, debt allowed) so a packet's timing never
                # depends on which path carried it
                direct = mask & ~queued_any & (
                    bootstrap | (n.tx_rem >= pkt.MTU)
                )
                # bootstrap sends are free, exactly like the pump path
                n = n.replace(
                    tx_rem=jnp.where(direct & ~bootstrap, n.tx_rem - size,
                                     n.tx_rem)
                )
                n = nic.count_tx(n, direct, size)
                state = state.with_sub(nic.SUB, n)
                state = self.disc.note_direct(state, direct, payload)
            remote = direct & (dst_host != hosts)
            wire = pkt.stamp(payload, direct, pkt.PDS_SENT)
            state = link.send(
                state, emitter, remote, dst_host.astype(jnp.int32), now64,
                KIND_PKT_DELIVER, wire, params,
                jnp.where(remote, size, 0),
                control_mask=payload[:, pkt.W_LEN] == 0,
            )
            lb = direct & (dst_host == hosts)
            emitter.emit(lb, now64, hosts, jnp.int32(KIND_PKT_DELIVER),
                         wire)

        enq = mask & ~direct
        with jax.named_scope(NIC_SCOPE):
            state, ok = self.disc.enqueue(
                state, enq, dst_host.astype(jnp.int32),
                pkt.stamp(payload, enq, pkt.PDS_NIC_QUEUED), now64,
            )
        state = pds_mod.record_drop(
            state, enq & ~ok, payload, pkt.PDS_DROPPED_SENDQ, now64
        )
        n = state.subs[nic.SUB]
        need = ok & ~n.send_pending
        emitter.emit(
            need, now64, hosts,
            jnp.int32(KIND_NIC_SEND), jnp.zeros_like(payload),
        )
        n = n.replace(send_pending=n.send_pending | need)
        return state.with_sub(nic.SUB, n), ok | direct

    # ---- runtime API (called from app handlers) ----

    def udp_sendto(
        self,
        state: SimState,
        emitter: Emitter,
        mask,
        now,
        dst_host,
        dst_port,
        src_port,
        size_bytes,
        socket_slot,
        payload=None,
        params: NetParams | None = None,
    ) -> SimState:
        """Queue a datagram on the sender's NIC and arm the send pump
        (transport_sendUserData → socket buffer → networkinterface_wantsSend).
        Apps may pass a prebuilt [H, P] payload (e.g. carrying timestamps in
        the spare words); ports/size args are ignored in that case."""
        hosts = state.host.gid
        H = hosts.shape[0]
        if payload is None:
            with jax.named_scope(UDP_SCOPE):
                payload = pkt.make_udp(
                    src_port=jnp.broadcast_to(
                        jnp.asarray(src_port, jnp.int32), (H,)),
                    dst_port=jnp.broadcast_to(
                        jnp.asarray(dst_port, jnp.int32), (H,)),
                    length=jnp.broadcast_to(
                        jnp.asarray(size_bytes, jnp.int32), (H,)),
                    priority=jnp.zeros((H,), jnp.int32),
                    src_host=hosts,
                    socket_slot=jnp.broadcast_to(
                        jnp.asarray(socket_slot, jnp.int32), (H,)
                    ),
                    payload_words=self.payload_words,
                )
        state, ok = self._tx(state, emitter, mask, now, dst_host, payload,
                             params=params)
        with jax.named_scope(UDP_SCOPE):
            u = udp.count_sent(
                state.subs[udp.SUB], ok,
                jnp.broadcast_to(jnp.asarray(socket_slot, jnp.int32), (H,)),
                payload,
            )
        return state.with_sub(udp.SUB, u)

    # ---- engine handlers ----

    def _deliver_local(self, state, mask, src, payload, emitter, now, params):
        """Demux + deliver + app hooks for packets that reached the NIC."""
        with jax.named_scope(UDP_SCOPE):
            u = state.subs[udp.SUB]
            is_udp = mask & (payload[:, pkt.W_PROTO] == pkt.PROTO_UDP)
            slot, found = udp.demux(u, is_udp, payload, src)
            u = udp.deliver(u, found, slot, payload)
            u = u.replace(
                drop_no_socket=u.drop_no_socket
                + jnp.sum(is_udp & ~found, dtype=jnp.int64)
            )
        c = state.counters
        state = state.replace(
            counters=c.replace(
                packets_delivered=c.packets_delivered + jnp.sum(mask, dtype=jnp.int64),
                bytes_delivered=c.bytes_delivered
                + jnp.sum(
                    jnp.where(mask, payload[:, pkt.W_LEN].astype(jnp.int64), 0)
                ),
            )
        )
        with jax.named_scope(NIC_SCOPE):
            state = state.with_sub(
                nic.SUB,
                nic.count_rx(
                    state.subs[nic.SUB], mask, pkt.total_bytes(payload)
                ),
            )
        state = state.with_sub(udp.SUB, u)
        state = pds_mod.record_delivery(state, mask, payload, now)
        for hook in self.recv_hooks:
            state = hook(state, found, slot, src, payload, emitter, now, params)
        if self.tcp is not None:
            is_tcp = mask & (payload[:, pkt.W_PROTO] == pkt.PROTO_TCP)
            state = self.tcp.on_segment(
                state, is_tcp, src, payload, emitter, now, params
            )
        return state

    def on_pkt_deliver(
        self, state: SimState, ev: EventView, emitter: Emitter, params: NetParams
    ) -> SimState:
        """Packet arrives at the destination: remote traffic enters the
        upstream router (CoDel); loopback skips straight to the socket.

        Uncontended fast path: empty router queue + rx tokens → the packet
        is delivered inside THIS micro-step (the reference's receive loop
        drains arrivals immediately when tokens allow,
        network_interface.c:448-485); the CoDel state updates applied are
        exactly those of dequeueing a zero-sojourn ("good") packet."""
        hosts = state.host.gid
        now = ev.time
        loopback = ev.mask & (ev.src == hosts)
        remote = ev.mask & (ev.src != hosts)

        n = state.subs[nic.SUB]
        r = state.subs[codel.SUB]
        with jax.named_scope(ROUTER_SCOPE):
            empty = ~codel.nonempty(r)
        with jax.named_scope(NIC_SCOPE):
            rx_rem, rx_tick = nic.lazy_refill(
                n.rx_rem, n.rx_tick, n.rx_refill, n.rx_cap, now, remote
            )
            n = n.replace(rx_rem=rx_rem, rx_tick=rx_tick)
            bootstrap = now < params.bootstrap_end
            size = pkt.total_bytes(ev.payload).astype(jnp.int64)
            direct = remote & empty & (bootstrap | (n.rx_rem >= pkt.MTU))
            n = n.replace(
                rx_rem=jnp.where(direct & ~bootstrap, n.rx_rem - size,
                                 n.rx_rem)
            )
        queued = remote & ~direct
        with jax.named_scope(ROUTER_SCOPE):
            # zero-sojourn dequeue semantics: good packet → interval reset,
            # drop-mode exit (codel.dequeue with sojourn 0 does exactly
            # this)
            r = r.replace(
                interval_expire=jnp.where(direct, 0, r.interval_expire),
                drop_mode=jnp.where(direct, False, r.drop_mode),
            )
            no_room = queued & ~codel.has_room(r)
        state = pds_mod.record_drop(
            state, no_room, ev.payload, pkt.PDS_DROPPED_OVERFLOW, now
        )
        with jax.named_scope(ROUTER_SCOPE):
            r = codel.enqueue(
                r, queued,
                pkt.stamp(ev.payload, queued, pkt.PDS_ROUTER_ENQUEUED),
                ev.src, now,
            )
        state = state.with_sub(codel.SUB, r).with_sub(nic.SUB, n)

        state = self._deliver_local(
            state, loopback | direct, ev.src, ev.payload, emitter, now, params
        )

        n = state.subs[nic.SUB]
        need = queued & ~n.recv_pending
        emitter.emit(
            need, now, hosts, jnp.int32(KIND_NIC_RECV),
            jnp.zeros_like(ev.payload),
        )
        n = n.replace(recv_pending=n.recv_pending | need)
        return state.with_sub(nic.SUB, n)

    # Packets drained per pump invocation. The reference's send loop drains
    # the qdisc while tokens allow within ONE task (network_interface.c:
    # 497-539); unrolling the same loop here keeps micro-step counts (and
    # thus full handler-suite invocations) ~BATCH× lower for bursty
    # traffic. 2 balances that against XLA compile time, which grows with
    # the unroll (the accelerator backend has no persistent compile cache).
    PUMP_BATCH = 1

    def on_nic_send(
        self, state: SimState, ev: EventView, emitter: Emitter, params: NetParams
    ) -> SimState:
        """Send pump: up to PUMP_BATCH packets per invocation while tokens
        allow; re-arms itself at `now` (more queued) or the next refill tick
        (tokens exhausted)."""
        hosts = state.host.gid
        now = ev.time
        mask = ev.mask
        n = state.subs[nic.SUB]
        n = n.replace(send_pending=n.send_pending & ~mask)

        with jax.named_scope(NIC_SCOPE):
            tx_rem, tx_tick = nic.lazy_refill(
                n.tx_rem, n.tx_tick, n.tx_refill, n.tx_cap, now, mask
            )
            n = n.replace(tx_rem=tx_rem, tx_tick=tx_tick)
        bootstrap = now < params.bootstrap_end
        state = state.with_sub(nic.SUB, n)

        for _ in range(self.PUMP_BATCH):
            with jax.named_scope(NIC_SCOPE):
                n = state.subs[nic.SUB]
                can = bootstrap | (n.tx_rem >= pkt.MTU)
                want = mask & can
                # the discipline owns head selection, the pop, AND any
                # dequeue-side drop policy (codel hook) — `do` marks hosts
                # that produced a deliverable packet this round
                state, do, payload, dst = self.disc.dequeue(state, now, want)

                # Charge the FULL wire size (may go negative — token debt).
                # For MTU-conformant packets this is identical to the
                # reference's clamp-at-zero (rem ≥ MTU ≥ size when the gate
                # passes); for oversize packets debt prevents exceeding
                # configured bandwidth.
                size = pkt.total_bytes(payload).astype(jnp.int64)
                n = state.subs[nic.SUB]
                n = n.replace(
                    tx_rem=jnp.where(do & ~bootstrap, n.tx_rem - size,
                                     n.tx_rem)
                )
                n = nic.count_tx(n, do, size)
                state = state.with_sub(nic.SUB, n)

            remote = do & (dst != hosts)
            wire = pkt.stamp(payload, do, pkt.PDS_SENT)
            state = link.send(
                state, emitter, remote, dst, now, KIND_PKT_DELIVER, wire,
                params, jnp.where(remote, size, 0),
                control_mask=payload[:, pkt.W_LEN] == 0,
            )
            # loopback: deliver at the same timestamp, no transit
            lb = do & (dst == hosts)
            emitter.emit(lb, now, hosts, jnp.int32(KIND_PKT_DELIVER), wire)

        with jax.named_scope(NIC_SCOPE):
            still = self.disc.nonempty(state)
        n = state.subs[nic.SUB]
        need = mask & still
        can_next = bootstrap | (n.tx_rem >= pkt.MTU)
        t_next = jnp.where(can_next, now, nic.next_refill_time(now))
        emitter.emit(
            need, t_next, hosts, jnp.int32(KIND_NIC_SEND),
            jnp.zeros_like(ev.payload),
        )
        n = n.replace(send_pending=n.send_pending | need)
        return state.with_sub(nic.SUB, n)

    def on_nic_recv(
        self, state: SimState, ev: EventView, emitter: Emitter, params: NetParams
    ) -> SimState:
        """Receive pump: CoDel-dequeue up to PUMP_BATCH packets per
        invocation while rx tokens allow; re-arms while the router queue is
        non-empty (network_interface.c:448-485 drains in one task too)."""
        hosts = state.host.gid
        now = ev.time
        mask = ev.mask
        n = state.subs[nic.SUB]
        n = n.replace(recv_pending=n.recv_pending & ~mask)

        with jax.named_scope(NIC_SCOPE):
            rx_rem, rx_tick = nic.lazy_refill(
                n.rx_rem, n.rx_tick, n.rx_refill, n.rx_cap, now, mask
            )
            n = n.replace(rx_rem=rx_rem, rx_tick=rx_tick)
        bootstrap = now < params.bootstrap_end

        for _ in range(self.recv_batch):
            can = bootstrap | (n.rx_rem >= pkt.MTU)
            want = mask & can

            with jax.named_scope(ROUTER_SCOPE):
                r = state.subs[codel.SUB]
                r, have, payload, src = codel.dequeue(
                    r, now, want, aqm=self.router_aqm
                )
            with jax.named_scope(NIC_SCOPE):
                size = pkt.total_bytes(payload).astype(jnp.int64)
                n = n.replace(
                    rx_rem=jnp.where(have & ~bootstrap, n.rx_rem - size,
                                     n.rx_rem)
                )
            state = state.with_sub(codel.SUB, r).with_sub(nic.SUB, n)

            state = self._deliver_local(
                state, have, src, payload, emitter, now, params
            )
            n = state.subs[nic.SUB]

        with jax.named_scope(ROUTER_SCOPE):
            still = codel.nonempty(state.subs[codel.SUB])
        need = mask & still
        can_next = bootstrap | (n.rx_rem >= pkt.MTU)
        t_next = jnp.where(can_next, now, nic.next_refill_time(now))
        emitter.emit(
            need, t_next, hosts, jnp.int32(KIND_NIC_RECV), jnp.zeros_like(payload)
        )
        n = n.replace(recv_pending=n.recv_pending | need)
        return state.with_sub(nic.SUB, n)

    # ---- gated arrival batching (engine bulk support) ----

    def bulk_kinds(self) -> dict | None:
        if self.deliver_batch <= 1:
            return None
        return {KIND_PKT_DELIVER: self.deliver_batch}

    def bulk_gate(self, state: SimState, params: NetParams, win_start,
                  win_end):
        """[H] i32: how many EXTRA consecutive arrivals each host may batch
        this micro-step, such that EVERY batched arrival provably takes
        on_pkt_deliver's direct path (no queueing → no sub-window self
        pump) and any app reply takes _tx's direct path (no send pump).

        Conservative by construction: token buckets are refilled only to
        win_start (mid-window refills are ignored), each arrival/reply is
        budgeted a full MTU, and any armed pump or non-empty queue zeroes
        the gate. An ineligible host simply falls back to one-event-per-
        micro-step — never incorrect, only slower."""
        from shadow_tpu.net import codel as codel_mod

        n = state.subs[nic.SUB]
        r = state.subs[codel_mod.SUB]
        ws = jnp.asarray(win_start, jnp.int64)
        G = self.deliver_batch
        rx_rem, _ = nic.lazy_refill(
            n.rx_rem, n.rx_tick, n.rx_refill, n.rx_cap, ws
        )
        tx_rem, _ = nic.lazy_refill(
            n.tx_rem, n.tx_tick, n.tx_refill, n.tx_cap, ws
        )
        # whole window inside bootstrap → tokens are not charged at all
        free = jnp.asarray(win_end, jnp.int64) <= params.bootstrap_end
        rx_cap_ev = jnp.where(
            free, G, (rx_rem // pkt.MTU).astype(jnp.int64)
        )
        tx_cap_ev = jnp.where(
            free, G, (tx_rem // pkt.MTU).astype(jnp.int64)
        )
        quiet = (
            ~codel_mod.nonempty(r)
            & ~self.disc.nonempty(state)
            & ~n.recv_pending
            & ~n.send_pending
        )
        cap = jnp.minimum(rx_cap_ev, tx_cap_ev) - 1  # head uses one budget
        return jnp.where(quiet, jnp.clip(cap, 0, G - 1), 0).astype(jnp.int32)

    def handlers(self) -> dict:
        h = {
            KIND_PKT_DELIVER: self.on_pkt_deliver,
            KIND_NIC_SEND: self.on_nic_send,
            KIND_NIC_RECV: self.on_nic_recv,
        }
        if self.tcp is not None:
            h.update(self.tcp.handlers())
        return h
