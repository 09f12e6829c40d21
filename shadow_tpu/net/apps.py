"""Device-side application models.

The reference runs real binaries (test_phold.c, tgen) as managed processes.
shadow_tpu supports that via the CPU interposition plane, but ALSO offers
fully on-device app models — vectorized behaviors that generate the same
traffic patterns with zero CPU↔TPU round-trips. These are the workloads for
the staged benchmark configs (BASELINE.md) and the analog of the reference's
PHOLD PDES canary (src/test/phold/test_phold.c: peers exchange
random-destination messages; msgload seeds circulate until runtime ends).
"""

from __future__ import annotations

import jax.numpy as jnp

from shadow_tpu.core import rng, simtime
from shadow_tpu.core.engine import Emitter, EventView, draw_uniform
from shadow_tpu.core.state import (
    KIND_APP_MSG,
    KIND_APP_TIMER,
    NetParams,
    SimState,
)
from shadow_tpu.net import link, packet as pkt


def ring_offset_dst(u, my_id, span, num_hosts):
    """Map a uniform draw to a destination a nonzero ring offset in
    [-span..-1, 1..span] away from my_id (mod num_hosts) — the shared
    topology-locality generator (PHOLD's local_span forwarding and any
    neighborhood-biased traffic shape)."""
    off = jnp.clip(
        jnp.floor(u * (2 * span)).astype(jnp.int32), 0, 2 * span - 1
    ) - span
    off = off + (off >= 0)  # skip 0
    return ((jnp.asarray(my_id, jnp.int32) + off) % num_hosts).astype(
        jnp.int32
    )


def locality_targets(num_hosts, anchors, local_span):
    """Static host→anchor table shaped by ring locality: hosts within
    local_span circular hops of some anchor target their nearest one
    (ties to the earlier anchor), the rest fall back to round-robin.
    local_span 0 = pure round-robin — the classic flood fan-in. Build-time
    numpy ([H] int32); riding in an app sub keeps it islands-shardable."""
    import numpy as np

    anchors = list(anchors)
    tgt = np.array(
        [anchors[i % len(anchors)] for i in range(num_hosts)],
        dtype=np.int32,
    )
    if local_span <= 0:
        return tgt
    for h in range(num_hosts):
        best, bd = None, None
        for a in anchors:
            d = abs(h - a)
            d = min(d, num_hosts - d)
            if bd is None or d < bd:
                best, bd = a, d
        if bd <= local_span:
            tgt[h] = best
    return tgt


class PholdApp:
    """PHOLD: each received message is forwarded to a random peer over the
    simulated network; message population = hosts × msgload; senders stop
    once sim time passes `runtime` (phold.yaml args: msgload, size, runtime).
    """

    SUB = "phold"
    # PHOLD events carry only a message size; right-sizing the payload
    # keeps the dominant per-window payload gathers 6x smaller than the
    # full packet-header layout
    PAYLOAD_WORDS = 2

    def __init__(
        self,
        num_hosts: int,
        msgload: int = 1,
        size_bytes: int = 64,
        start_time: int = simtime.NS_PER_SEC,
        runtime: int = 5 * simtime.NS_PER_SEC,
        hot_frac: float = 0.0,
        hot_share: float = 0.0,
        local_span: int = 0,
    ):
        self.num_hosts = num_hosts
        self.msgload = msgload
        self.size_bytes = size_bytes
        self.start_time = start_time
        self.stop_sending = start_time + runtime
        # Locality-biased variant (the async-sync benchmark shape, and
        # the communication structure of relay-mesh workloads): forwards
        # target a ring neighborhood of +-local_span host ids around the
        # sender instead of the uniform all-to-all. 0 = classic PHOLD.
        self.local_span = int(local_span)
        if self.local_span < 0 or self.local_span >= num_hosts:
            raise ValueError(
                "phold local_span must be in [0, num_hosts)"
            )
        if self.local_span and (hot_frac > 0 or hot_share > 0):
            raise ValueError(
                "phold local_span and hot_frac/hot_share are exclusive"
            )
        # Skewed-destination variant (the work-stealing benchmark shape,
        # scheduler_policy_host_steal.c's raison d'etre): hot_share of
        # all messages target the first hot_frac of hosts. hot_frac 0 =
        # classic uniform PHOLD. The hot variant permits self-sends
        # (they arrive at +latency, respecting the bulk contract).
        self.hot_frac = float(hot_frac)
        self.hot_share = float(hot_share)
        if (self.hot_frac > 0) != (self.hot_share > 0):
            raise ValueError(
                "phold hot_frac and hot_share must be set together"
            )
        if not (0.0 <= self.hot_share < 1.0) or not (
            0.0 <= self.hot_frac <= 1.0
        ):
            raise ValueError("hot_share must be in [0,1), hot_frac in [0,1]")
        self.hot_n = (
            max(1, int(num_hosts * self.hot_frac))
            if self.hot_frac > 0 else 0
        )

    def init_sub(self) -> dict:
        H = self.num_hosts
        return {
            "received": jnp.zeros((H,), dtype=jnp.int64),
            "forwarded": jnp.zeros((H,), dtype=jnp.int64),
        }

    def bulk_kinds(self) -> dict[int, int]:
        """KIND_APP_MSG qualifies for the engine's bulk batch (it never
        emits a self event inside the window: forwards go to OTHER hosts,
        and even the H==1 self-loop lands at +latency >= window end). A
        host's per-window wave is ~Poisson(msgload); 2×msgload covers the
        tail without bloating the unrolled handler."""
        return {KIND_APP_MSG: min(2 * self.msgload, 16)}

    def initial_events(self):
        """msgload seed messages per host, self-delivered at start_time; the
        first processing forwards each to a random peer."""
        out = []
        for h in range(self.num_hosts):
            for _ in range(self.msgload):
                out.append(
                    (self.start_time, h, h, KIND_APP_MSG, [self.size_bytes])
                )
        return out

    def handle_msg(
        self, state: SimState, ev: EventView, emitter: Emitter, params: NetParams
    ) -> SimState:
        H = self.num_hosts  # GLOBAL host count (destination id range)
        hosts = state.host.gid  # global ids of this shard's rows
        sub = state.subs[self.SUB]
        sub = dict(sub)
        sub["received"] = sub["received"] + ev.mask.astype(jnp.int64)

        send_mask = ev.mask & (ev.time < self.stop_sending)
        state, u = draw_uniform(state, send_mask)
        dst = self._pick_dst(u, hosts)
        sub["forwarded"] = sub["forwarded"] + send_mask.astype(jnp.int64)
        state = state.with_sub(self.SUB, sub)
        return link.send(
            state,
            emitter,
            send_mask,
            dst,
            ev.time,
            KIND_APP_MSG,
            ev.payload,
            params,
            self.size_bytes,
        )

    def _pick_dst(self, u, my_id):
        """Map one uniform draw to a destination. Uniform mode skips self
        exactly like the reference's `(me + 1 + rand%(H-1)) %% H`; the hot
        variant splits the unit interval at hot_share; the local_span
        variant draws a nonzero ring offset in [-span, span]."""
        H = self.num_hosts
        if self.local_span > 0:
            return ring_offset_dst(u, my_id, self.local_span, H)
        if self.hot_n > 0:
            hs = self.hot_share
            nh = self.hot_n
            hot = jnp.floor(u / hs * nh).astype(jnp.int32)
            cold = nh + jnp.floor(
                (u - hs) / (1.0 - hs) * (H - nh)
            ).astype(jnp.int32)
            return jnp.clip(
                jnp.where(u < hs, hot, cold), 0, H - 1
            )
        if H <= 1:
            return jnp.broadcast_to(jnp.asarray(my_id), u.shape)
        dst = jnp.clip(jnp.floor(u * (H - 1)).astype(jnp.int32), 0, H - 2)
        return dst + (dst >= my_id)  # skip self

    def handlers(self):
        return {KIND_APP_MSG: self.handle_msg}

    def handle_msg_matrix(self, state, mv, emitter, params):
        """Whole-window vectorized form of handle_msg over [H, K] columns
        (the engine's matrix fast path). Reproduces the sequential
        per-event draw schedule bit-for-bit: event k's (dst, reliability)
        draws use counters c0 + 2·(#sends before k) and +1 — REQUIRES an
        all-reachable topology so every send costs exactly two draws
        (sim.py only registers this handler when that holds)."""
        H = self.num_hosts  # GLOBAL host count (destination id range)
        hosts = state.host.gid
        sub = dict(state.subs[self.SUB])
        sub["received"] = sub["received"] + jnp.sum(
            mv.mask, axis=1, dtype=jnp.int64
        )
        send = mv.mask & (mv.time < self.stop_sending)  # [H, K]
        si = send.astype(jnp.uint32)
        excl = jnp.cumsum(si, axis=1) - si
        c0 = state.host.rng_counter
        off = c0[:, None] + 2 * excl
        u1 = rng.uniform_matrix(state.rng_keys, off)
        u2 = rng.uniform_matrix(state.rng_keys, off + 1)
        state = state.replace(
            host=state.host.replace(
                rng_counter=c0 + 2 * jnp.sum(si, axis=1, dtype=jnp.uint32)
            )
        )
        dst = self._pick_dst(u1, hosts[:, None])
        sub["forwarded"] = sub["forwarded"] + jnp.sum(
            send, axis=1, dtype=jnp.int64
        )
        state = state.with_sub(self.SUB, sub)
        # link.send in matrix form (worker.c:517-576): latency lookup,
        # reliability roll, delivery emission. Single-vertex topologies
        # broadcast; the general case reads the replicated global
        # host->vertex table (params.vertex_g) so dst — a GLOBAL id —
        # never indexes the shard-local vertex array.
        if params.latency_vv.shape[0] == 1:
            lat = jnp.broadcast_to(params.latency_vv[0, 0], dst.shape)
            rel = jnp.broadcast_to(params.reliability_vv[0, 0], dst.shape)
        else:
            vd = (
                params.vertex_g[dst]
                if params.vertex_g is not None
                else state.host.vertex[dst]
            )  # [H, K]
            vs = jnp.broadcast_to(state.host.vertex[:, None], vd.shape)
            lat = params.latency_vv[vs, vd]
            rel = params.reliability_vv[vs, vd]
        kept = (mv.time < params.bootstrap_end) | (u2 < rel)
        emitter.emit(
            send & kept, mv.time + lat, dst, jnp.int32(KIND_APP_MSG),
            mv.payload,
        )
        c = state.counters
        return state.replace(
            counters=c.replace(
                packets_sent=c.packets_sent + jnp.sum(send, dtype=jnp.int64),
                packets_dropped_loss=c.packets_dropped_loss
                + jnp.sum(send & ~kept, dtype=jnp.int64),
                bytes_sent=c.bytes_sent + jnp.int64(self.size_bytes)
                * jnp.sum(send, dtype=jnp.int64),
            )
        )

    def matrix_handlers(self):
        return {KIND_APP_MSG: self.handle_msg_matrix}


SERVER_PORT = 9000
CLIENT_PORT_BASE = 40000


class UdpFloodApp:
    """BASELINE config 2: client hosts flood a server with UDP datagrams at a
    fixed rate through the full NIC/router/token-bucket path.

    role[h]: 0 = server (binds SERVER_PORT), 1 = client (timer-driven sends).
    """

    SUB = "udp_flood"

    def __init__(
        self,
        num_hosts: int,
        server_hosts,  # list[int]
        interval_ns: int,
        size_bytes: int = 1024,
        start_time: int = simtime.NS_PER_SEC,
        stop_sending: int | None = None,
        local_span: int = 0,
        start_offsets=None,
    ):
        self.num_hosts = num_hosts
        self.server_hosts = list(server_hosts)
        # [H] ns after start_time at which each client's first send falls
        # (sim._start_offsets staggers them); None starts all at once
        self.start_offsets = start_offsets
        self.interval_ns = int(interval_ns)
        self.size_bytes = int(size_bytes)
        # locality-shaped fan-in: clients within local_span ring hops of a
        # server flood THAT server (the incast aggregation shape); 0 keeps
        # the classic round-robin spread
        self.local_span = int(local_span)
        if self.local_span < 0 or self.local_span >= num_hosts:
            raise ValueError(
                "udp_flood local_span must be in [0, num_hosts)"
            )
        if self.size_bytes > pkt.MTU - pkt.UDP_HEADER_BYTES:
            raise ValueError(
                f"datagram size {self.size_bytes} exceeds MTU payload "
                f"{pkt.MTU - pkt.UDP_HEADER_BYTES} (fragmentation unsupported)"
            )
        self.start_time = int(start_time)
        self.stop_sending = stop_sending

    def attach(self, stack):
        self.stack = stack
        import numpy as np

        role = np.ones(self.num_hosts, dtype=np.int32)
        role[self.server_hosts] = 0
        self._role = jnp.asarray(role)
        # clients target servers round-robin, locality-biased when
        # local_span is set
        self._target = jnp.asarray(
            locality_targets(
                self.num_hosts, self.server_hosts, self.local_span
            )
        )
        stack.bind_udp(np.flatnonzero(role == 0), 0, SERVER_PORT)
        stack.bind_udp(np.flatnonzero(role == 1), 0, CLIENT_PORT_BASE)

    def init_sub(self) -> dict:
        H = self.num_hosts
        # role/target ride in the sub-state (not python closures) so the
        # islands engine shards them with every other [H]-leading array
        return {
            "sent": jnp.zeros((H,), jnp.int64),
            "recv": jnp.zeros((H,), jnp.int64),
            "role": self._role,
            "target": self._target,
        }

    def initial_events(self):
        off = self.start_offsets
        return [
            (self.start_time + (0 if off is None else int(off[h])), h, h,
             KIND_APP_TIMER, [])
            for h in range(self.num_hosts)
            if int(self._role[h]) == 1
        ]

    def on_timer(self, state, ev, emitter, params):
        sub = dict(state.subs[self.SUB])
        send = ev.mask & (sub["role"] == 1)
        if self.stop_sending is not None:
            send = send & (ev.time < self.stop_sending)
        sub["sent"] = sub["sent"] + send.astype(jnp.int64)
        state = state.with_sub(self.SUB, sub)
        state = self.stack.udp_sendto(
            state, emitter, send, ev.time, sub["target"], SERVER_PORT,
            CLIENT_PORT_BASE, self.size_bytes, 0,
            params=params,
        )
        emitter.emit(
            send, ev.time + self.interval_ns, state.host.gid,
            jnp.int32(KIND_APP_TIMER), ev.payload,
        )
        return state

    def on_receive(self, state, mask, slot, src, payload, emitter, now, params):
        sub = dict(state.subs[self.SUB])
        got = mask & (sub["role"] == 0)
        sub["recv"] = sub["recv"] + got.astype(jnp.int64)
        return state.with_sub(self.SUB, sub)

    def handlers(self):
        return {KIND_APP_TIMER: self.on_timer}


class UdpEchoApp:
    """BASELINE config 1 analog (tgen-echo style): clients send a datagram to
    the server every interval; the server echoes it back; clients accumulate
    round-trip samples. Exercises both directions of the NIC path."""

    SUB = "udp_echo"

    def __init__(
        self,
        num_hosts: int,
        server_host: int,
        interval_ns: int,
        size_bytes: int = 512,
        start_time: int = simtime.NS_PER_SEC,
        stop_sending: int | None = None,
    ):
        self.num_hosts = num_hosts
        self.server_host = int(server_host)
        self.interval_ns = int(interval_ns)
        self.size_bytes = int(size_bytes)
        if self.size_bytes > pkt.MTU - pkt.UDP_HEADER_BYTES:
            raise ValueError(
                f"datagram size {self.size_bytes} exceeds MTU payload "
                f"{pkt.MTU - pkt.UDP_HEADER_BYTES} (fragmentation unsupported)"
            )
        self.start_time = int(start_time)
        self.stop_sending = stop_sending

    def attach(self, stack):
        self.stack = stack
        import numpy as np

        role = np.ones(self.num_hosts, dtype=np.int32)
        role[self.server_host] = 0
        self._role = jnp.asarray(role)
        stack.bind_udp(self.server_host, 0, SERVER_PORT)
        stack.bind_udp(np.flatnonzero(role == 1), 0, CLIENT_PORT_BASE)

    def init_sub(self) -> dict:
        H = self.num_hosts
        return {
            "sent": jnp.zeros((H,), jnp.int64),
            "echoed": jnp.zeros((H,), jnp.int64),
            "rtt_sum": jnp.zeros((H,), jnp.int64),
            "rtt_count": jnp.zeros((H,), jnp.int64),
            "role": self._role,
        }

    def initial_events(self):
        return [
            (self.start_time, h, h, KIND_APP_TIMER, [])
            for h in range(self.num_hosts)
            if h != self.server_host
        ]

    def on_timer(self, state, ev, emitter, params):
        hosts = state.host.gid
        H = hosts.shape[0]
        sub = dict(state.subs[self.SUB])
        send = ev.mask & (sub["role"] == 1)
        if self.stop_sending is not None:
            send = send & (ev.time < self.stop_sending)
        sub["sent"] = sub["sent"] + send.astype(jnp.int64)
        state = state.with_sub(self.SUB, sub)
        # The send timestamp travels IN the datagram (spare seq/ack words)
        # and the server echoes it back — RTT is then exact even when
        # multiple requests are in flight.
        req = pkt.make_udp(
            src_port=jnp.full((H,), CLIENT_PORT_BASE, jnp.int32),
            dst_port=jnp.full((H,), SERVER_PORT, jnp.int32),
            length=jnp.full((H,), self.size_bytes, jnp.int32),
            priority=jnp.zeros((H,), jnp.int32),
            src_host=hosts,
            socket_slot=jnp.zeros((H,), jnp.int32),
            payload_words=self.stack.payload_words,
        )
        req = pkt.pack_time(req, jnp.where(send, ev.time, 0))
        state = self.stack.udp_sendto(
            state, emitter, send, ev.time,
            jnp.full((H,), self.server_host, jnp.int32),
            SERVER_PORT, CLIENT_PORT_BASE, self.size_bytes, 0, payload=req,
            params=params,
        )
        emitter.emit(
            send, ev.time + self.interval_ns, hosts,
            jnp.int32(KIND_APP_TIMER), ev.payload,
        )
        return state

    def on_receive(self, state, mask, slot, src, payload, emitter, now, params):
        hosts = state.host.gid
        sub = dict(state.subs[self.SUB])
        # server: echo back to (src, src_port), preserving the timestamp words
        server_got = mask & (sub["role"] == 0)
        sub["echoed"] = sub["echoed"] + server_got.astype(jnp.int64)
        # client: RTT from the echoed timestamp
        client_got = mask & (sub["role"] == 1)
        rtt = now - pkt.unpack_time(payload)
        sub["rtt_sum"] = sub["rtt_sum"] + jnp.where(client_got, rtt, 0)
        sub["rtt_count"] = sub["rtt_count"] + client_got.astype(jnp.int64)
        state = state.with_sub(self.SUB, sub)
        reply = payload
        reply = reply.at[:, pkt.W_SRC_PORT].set(SERVER_PORT)
        reply = reply.at[:, pkt.W_DST_PORT].set(payload[:, pkt.W_SRC_PORT])
        reply = reply.at[:, pkt.W_SRC_HOST].set(hosts)
        state = self.stack.udp_sendto(
            state, emitter, server_got, now, src,
            None, None, None, 0, payload=reply,
            params=params,
        )
        return state

    def handlers(self):
        return {KIND_APP_TIMER: self.on_timer}


class TcpBulkApp:
    """BASELINE config 3: each client opens a TCP connection to a server and
    pushes `total_bytes` through the congestion-controlled stream, then
    closes. Exercises handshake, Reno, retransmission, and teardown.

    Server hosts listen on SERVER_PORT (slot 0); child sockets are allocated
    per accepted connection, so a server needs sockets_per_host > its client
    count. Clients connect from slot 0 at start_time.
    """

    SUB = "tcp_bulk"

    def __init__(
        self,
        num_hosts: int,
        server_hosts,
        total_bytes: int,
        start_time: int = simtime.NS_PER_SEC,
    ):
        self.num_hosts = num_hosts
        self.server_hosts = list(server_hosts)
        self.total_bytes = int(total_bytes)
        self.start_time = int(start_time)

    def attach(self, stack):
        self.stack = stack
        import numpy as np

        role = np.ones(self.num_hosts, dtype=np.int32)
        role[self.server_hosts] = 0
        self._role = jnp.asarray(role)
        tgt = np.array(
            [
                self.server_hosts[i % len(self.server_hosts)]
                for i in range(self.num_hosts)
            ],
            dtype=np.int32,
        )
        self._target = jnp.asarray(tgt)
        for s in self.server_hosts:
            stack.tcp_listen(s, 0, SERVER_PORT)
        stack.tcp.on_established(self.on_established)
        stack.tcp.on_peer_fin(self.on_peer_fin)

    def init_sub(self) -> dict:
        H = self.num_hosts
        return {
            "connected": jnp.zeros((H,), jnp.int64),
            "accepted": jnp.zeros((H,), jnp.int64),
            "eof_seen": jnp.zeros((H,), jnp.int64),
            "role": self._role,
            "target": self._target,
        }

    def initial_events(self):
        return [
            (self.start_time, h, h, KIND_APP_TIMER, [])
            for h in range(self.num_hosts)
            if int(self._role[h]) == 1
        ]

    def on_timer(self, state, ev, emitter, params):
        """Client start: active open toward the target server."""
        sub = state.subs[self.SUB]
        go = ev.mask & (sub["role"] == 1)
        state = self.stack.tcp.connect(
            state, emitter, go, jnp.zeros_like(sub["role"]),
            sub["target"], SERVER_PORT, CLIENT_PORT_BASE, ev.time,
            params=params,
        )
        return state

    def on_established(self, state, mask, slot, is_accept, src, now, emitter,
                       params):
        sub = dict(state.subs[self.SUB])
        client_up = mask & ~is_accept & (sub["role"] == 1)
        sub["connected"] = sub["connected"] + client_up.astype(jnp.int64)
        sub["accepted"] = sub["accepted"] + (
            mask & is_accept & (sub["role"] == 0)
        ).astype(jnp.int64)
        state = state.with_sub(self.SUB, sub)
        # write the whole stream into sequence space; FIN rides after it
        state = self.stack.tcp.send_app(
            state, emitter, client_up, slot, self.total_bytes, now
        )
        state = self.stack.tcp.close_app(state, emitter, client_up, slot, now)
        return state

    def on_peer_fin(self, state, mask, slot, now, emitter, params):
        """Server side: client finished sending → close our half too."""
        sub = dict(state.subs[self.SUB])
        srv = mask & (sub["role"] == 0)
        sub["eof_seen"] = sub["eof_seen"] + srv.astype(jnp.int64)
        state = state.with_sub(self.SUB, sub)
        state = self.stack.tcp.close_app(state, emitter, srv, slot, now)
        return state

    def handlers(self):
        return {KIND_APP_TIMER: self.on_timer}
