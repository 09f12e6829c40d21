"""The CPU↔TPU seam: managed-process traffic through the device network.

This is the BASELINE north star (SURVEY.md header): keep syscall-emulated
host processes on the CPU, but lift the network hot path — NIC token
buckets, CoDel router queues, port demux, latency/loss path model, and the
full TCP state machine — onto the device engine, with the Router/Topology
boundary as the handoff.

Protocol (conservative, deadlock-free):

- Managed sendto()/send() calls append injection records host-side; payload
  BYTES stay in host-side buffers — the device moves 12-word packet headers
  and sequence space only (UDP rides a claim ticket in W_HANDLE; TCP bytes
  are matched to device-reported in-order advances, which is sound because
  TCP delivers in order by construction).
- When every process is parked, the driver syncs: pending injections enter
  the device event pool as KIND_PROC_SYSCALL events at their send times,
  and the device steps conservative windows until the first batch of
  outputs lands (or its pool drains past the driver's next local event).
  Output rows (UDP deliveries, TCP establishment/receive/EOF notifications)
  drain from per-host rings and become ordinary driver wakeups at their
  device-computed times.
- Injections that land behind the device's completed window are processed
  one window late with their true timestamps — the engine's documented
  deferral semantics; their effects still land at t + latency ≥ the
  next window, so causality holds (window length ≤ min path latency).

Port binds/unbinds and TCP listens from syscalls update the device socket
tables host-side between dispatches (bind is rare; the hot path stays
compiled). TCP slot space is partitioned: the CPU plane allocates
active-open slots in [0, child_base); the device allocates accept-side
children in [child_base, S) (tcp.py child_base), so a pending connect
injection can never collide with a device-side accept.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core import simtime, soa
from shadow_tpu.core.engine import Simulation, _set_col
from shadow_tpu.core.state import KIND_PROC_SYSCALL, NetParams
from shadow_tpu.net import packet as pkt, tcp as tcp_mod, udp
from shadow_tpu.net.stack import NetStack
from shadow_tpu.net.tcp import _g

NEVER = simtime.NEVER

BRIDGE_SUB = "bridge"

# Injection opcodes riding in W_PROTO of KIND_PROC_SYSCALL payloads.
# OP_UDP doubles as the wire protocol number so the payload row IS the
# datagram; TCP ops are control rows interpreted by the inject handler.
OP_UDP = pkt.PROTO_UDP
OP_TCP_CONNECT = 1
OP_TCP_SEND = 2
OP_TCP_CLOSE = 3


@dataclass
class Delivery:
    """A UDP datagram reached a bound device socket."""

    time: int
    dst_host: int
    src_host: int
    src_port: int
    dst_port: int
    length: int
    handle: int


@dataclass
class TcpEstablished:
    """A device TCP connection reached ESTABLISHED on `host`."""

    time: int
    host: int
    slot: int
    peer_host: int
    peer_port: int
    local_port: int
    is_accept: bool


@dataclass
class TcpBytes:
    """`nbytes` new in-order stream bytes arrived at (host, slot)."""

    time: int
    host: int
    slot: int
    nbytes: int


@dataclass
class TcpFin:
    """Peer FIN consumed at (host, slot): EOF after all data.

    ``time_wait`` means the consume moved the socket into TIME_WAIT — both
    FINs are exchanged and acked, so the CPU plane can recycle its slot
    mirror immediately instead of waiting out the 60 s device timer."""

    time: int
    host: int
    slot: int
    time_wait: bool = False


@dataclass
class TcpClosed:
    """The device freed (host, slot): orderly close completed (reset=False)
    or the connection was torn down by RST / refused (reset=True)."""

    time: int
    host: int
    slot: int
    reset: bool


# drain ordering at equal timestamps: establishment before data before
# EOF before teardown
_EVENT_RANK = {
    TcpEstablished: 0, Delivery: 1, TcpBytes: 1, TcpFin: 2, TcpClosed: 3,
}


class DeviceNetBridge:
    """Owns the device Simulation that carries managed-process traffic."""

    def __init__(
        self,
        *,
        baked,
        bw_up_bits,
        bw_down_bits,
        host_vertex,
        seed: int,
        stop_time: int,
        bootstrap_end: int = 0,
        sockets_per_host: int = 16,
        event_capacity: int = 4096,
        K: int = 16,
        ring_slots: int | None = None,
        with_tcp: bool = False,
        router_queue_slots: int = 64,
        router_variant: str = "codel",
    ):
        H = len(host_vertex)
        if ring_slots is None:
            # a window can deliver up to K datagrams per host
            ring_slots = max(32, 2 * K)
        self.H = H
        self.S = sockets_per_host
        self.R = ring_slots
        self.with_tcp = with_tcp
        self.child_base = sockets_per_host // 2 if with_tcp else 0
        stack = NetStack(
            H,
            jnp.asarray(bw_up_bits),
            jnp.asarray(bw_down_bits),
            sockets_per_host=sockets_per_host,
            router_queue_slots=router_queue_slots,
            router_variant=router_variant,
            with_tcp=with_tcp,
            tcp_child_base=self.child_base,
        )
        self.stack = stack
        stack.on_receive(self._on_recv)
        if with_tcp:
            stack.tcp.on_established(self._on_tcp_established)
            stack.tcp.on_receive(self._on_tcp_bytes)
            stack.tcp.on_peer_fin(self._on_tcp_fin)
            stack.tcp.on_reset(self._on_tcp_reset)
            stack.tcp.on_closed(self._on_tcp_closed)
        handlers = dict(stack.handlers())
        handlers[KIND_PROC_SYSCALL] = self._on_inject
        subs = stack.init_subs()
        R = ring_slots
        br = {
            # UDP delivery ring
            "time": jnp.full((H, R), NEVER, jnp.int64),
            "src_host": jnp.zeros((H, R), jnp.int32),
            "src_port": jnp.zeros((H, R), jnp.int32),
            "dst_port": jnp.zeros((H, R), jnp.int32),
            "length": jnp.zeros((H, R), jnp.int32),
            "handle": jnp.zeros((H, R), jnp.int32),
            "count": jnp.zeros((H,), jnp.int32),
            "overflow": jnp.zeros((), jnp.int64),
        }
        if with_tcp:
            br.update({
                # establishment ring
                "e_time": jnp.full((H, R), NEVER, jnp.int64),
                "e_slot": jnp.zeros((H, R), jnp.int32),
                "e_peer_host": jnp.zeros((H, R), jnp.int32),
                "e_peer_port": jnp.zeros((H, R), jnp.int32),
                "e_local_port": jnp.zeros((H, R), jnp.int32),
                "e_accept": jnp.zeros((H, R), bool),
                "e_count": jnp.zeros((H,), jnp.int32),
                # in-order byte-advance ring
                "r_time": jnp.full((H, R), NEVER, jnp.int64),
                "r_slot": jnp.zeros((H, R), jnp.int32),
                "r_bytes": jnp.zeros((H, R), jnp.int32),
                "r_count": jnp.zeros((H,), jnp.int32),
                # peer-FIN (EOF) ring
                "f_time": jnp.full((H, R), NEVER, jnp.int64),
                "f_slot": jnp.zeros((H, R), jnp.int32),
                "f_tw": jnp.zeros((H, R), bool),
                "f_count": jnp.zeros((H,), jnp.int32),
                # teardown ring (orderly close completion or RST)
                "c_time": jnp.full((H, R), NEVER, jnp.int64),
                "c_slot": jnp.zeros((H, R), jnp.int32),
                "c_reset": jnp.zeros((H, R), bool),
                "c_count": jnp.zeros((H,), jnp.int32),
            })
        subs[BRIDGE_SUB] = br
        params = NetParams(
            latency_vv=jnp.asarray(baked.latency_vv),
            reliability_vv=jnp.asarray(baked.reliability_vv),
            bootstrap_end=jnp.int64(bootstrap_end),
        )
        self.sim = Simulation(
            num_hosts=H,
            handlers=handlers,
            params=params,
            host_vertex=np.asarray(host_vertex),
            seed=seed,
            stop_time=stop_time,
            runahead=baked.min_latency_ns,
            event_capacity=event_capacity,
            K=K,
            subs=subs,
        )
        self._pending: list[tuple[int, int, np.ndarray]] = []  # (t, src, row)
        self._drained = False  # device pool empty since the last injection
        self._ring_prefixes = [""] + (
            ["e_", "r_", "f_", "c_"] if with_tcp else []
        )
        # Fused sync loop: ONE device dispatch advances many windows, exiting
        # early as soon as any output ring holds a row. Replaces the
        # window-per-dispatch round trips that dominated managed-plane wall
        # time (docs/bench_notes.md round 2): every dispatch is a host sync.
        self._sync_max_windows = 32
        self._run_sync = jax.jit(self._make_run_sync())
        self._handles: dict[int, bytes] = {}
        self._next_handle = 1
        self._port_slot: dict[tuple[int, int], int] = {}
        self._inflight = 0  # injected minus delivered UDP datagrams (drops
        # reconciled when the device drains — see sync())
        self._overflow_seen = 0
        # TCP host-side slot mirror: free active-open slots per host
        self._tcp_free: list[list[int]] = [
            list(range(self.child_base - 1, -1, -1)) for _ in range(H)
        ]
        # (host, slot) pairs the CPU believes are live on device (listeners,
        # active opens, accepted children); while non-empty, sync() must let
        # the device advance (timers/retransmits may be pending)
        self._tcp_live: set[tuple[int, int]] = set()

    def _make_run_sync(self):
        """Build the fused device sync loop: step conservative windows until
        (a) any output ring holds a row, (b) the pool drains past `horizon`,
        or (c) max_windows elapse (bounds dispatch length for the
        accelerator watchdog). Returns (state, min_next, out_rows)."""
        step = self.sim._step_fn
        runahead = jnp.int64(self.sim.runahead)
        prefixes = list(self._ring_prefixes)

        def out_count(state):
            br = state.subs[BRIDGE_SUB]
            tot = jnp.zeros((), jnp.int32)
            for p in prefixes:
                tot = tot + jnp.sum(br[f"{p}count"], dtype=jnp.int32)
            return tot

        def run_sync(state, params, horizon, max_windows):
            horizon = jnp.asarray(horizon, jnp.int64)
            max_windows = jnp.asarray(max_windows, jnp.int32)

            def cond(c):
                state, mn, w = c
                return (
                    (out_count(state) == 0) & (mn < horizon)
                    & (w < max_windows)
                )

            def body(c):
                state, mn, w = c
                we = jnp.minimum(mn + runahead, horizon)
                state, mn2 = step(state, params, mn, we)
                return state, mn2, w + 1

            mn0 = jnp.min(state.pool.time)
            state, mn, _ = jax.lax.while_loop(
                cond, body, (state, mn0, jnp.int32(0))
            )
            return state, mn, out_count(state)

        return run_sync

    # ------------------------------------------------------------------
    # device-side handlers
    # ------------------------------------------------------------------

    def _on_inject(self, state, ev, emitter, params):
        """A managed syscall enters the device network. The opcode rides in
        W_PROTO: a UDP row is the datagram itself (dst host in W_SEQ); TCP
        control rows drive the device TCP machine."""
        op = ev.payload[:, pkt.W_PROTO]
        m_udp = ev.mask & (op == OP_UDP)
        dst = ev.payload[:, pkt.W_SEQ]
        payload = ev.payload.at[:, pkt.W_SEQ].set(0)
        state = self.stack.udp_sendto(
            state, emitter, m_udp, ev.time, dst,
            dst_port=0, src_port=0, size_bytes=0,
            socket_slot=ev.payload[:, pkt.W_SOCKET],
            payload=payload, params=params,
        )
        if self.with_tcp:
            tcp = self.stack.tcp
            slot = ev.payload[:, pkt.W_SOCKET]
            m_conn = ev.mask & (op == OP_TCP_CONNECT)
            state = tcp.connect(
                state, emitter, m_conn, slot,
                dst_host=ev.payload[:, pkt.W_SEQ],
                dst_port=ev.payload[:, pkt.W_DST_PORT],
                local_port=ev.payload[:, pkt.W_SRC_PORT],
                now=ev.time, params=params,
            )
            m_send = ev.mask & (op == OP_TCP_SEND)
            state = tcp.send_app(
                state, emitter, m_send, slot, ev.payload[:, pkt.W_LEN],
                ev.time,
            )
            m_close = ev.mask & (op == OP_TCP_CLOSE)
            state = tcp.close_app(state, emitter, m_close, slot, ev.time)
        return state

    def _ring_append(self, state, prefix: str, mask, cols: dict):
        """Append one row per masked host to the `prefix` ring; overflow is
        counted (and warned about at drain time)."""
        br = state.subs[BRIDGE_SUB]
        cnt = br[f"{prefix}count"]
        fits = mask & (cnt < self.R)
        col = jnp.clip(cnt, 0, self.R - 1)
        new = dict(br)
        for name, val in cols.items():
            key = f"{prefix}{name}"
            new[key] = _set_col(br[key], col, fits, val)
        new[f"{prefix}count"] = cnt + fits.astype(jnp.int32)
        new["overflow"] = br["overflow"] + jnp.sum(mask & ~fits,
                                                  dtype=jnp.int64)
        return state.with_sub(BRIDGE_SUB, new)

    def _on_recv(self, state, found, slot, src, payload, emitter, now, params):
        """A datagram reached a bound UDP socket: record it in the delivered
        ring for the CPU plane to drain."""
        nowv = jnp.broadcast_to(now, found.shape).astype(jnp.int64)
        return self._ring_append(state, "", found, {
            "time": nowv,
            "src_host": src.astype(jnp.int32),
            "src_port": payload[:, pkt.W_SRC_PORT],
            "dst_port": payload[:, pkt.W_DST_PORT],
            "length": payload[:, pkt.W_LEN],
            "handle": payload[:, pkt.W_HANDLE],
        })

    def _on_tcp_established(self, state, mask, slot, is_accept, src, now,
                            emitter, params):
        t = state.subs[tcp_mod.SUB]
        nowv = jnp.broadcast_to(now, mask.shape).astype(jnp.int64)
        return self._ring_append(state, "e_", mask, {
            "time": nowv,
            "slot": slot.astype(jnp.int32),
            "peer_host": _g(t.peer_host, slot),
            "peer_port": _g(t.peer_port, slot),
            "local_port": _g(t.local_port, slot),
            "accept": is_accept,
        })

    def _on_tcp_bytes(self, state, mask, slot, nbytes, src, now, emitter,
                      params):
        nowv = jnp.broadcast_to(now, mask.shape).astype(jnp.int64)
        return self._ring_append(state, "r_", mask & (nbytes > 0), {
            "time": nowv,
            "slot": slot.astype(jnp.int32),
            "bytes": nbytes.astype(jnp.int32),
        })

    def _on_tcp_fin(self, state, mask, slot, now, emitter, params):
        t = state.subs[tcp_mod.SUB]
        nowv = jnp.broadcast_to(now, mask.shape).astype(jnp.int64)
        return self._ring_append(state, "f_", mask, {
            "time": nowv,
            "slot": slot.astype(jnp.int32),
            # hooks run after the consume transition, so this reads the
            # post-FIN state
            "tw": _g(t.state, slot) == tcp_mod.TIME_WAIT,
        })

    def _on_tcp_reset(self, state, mask, slot, now, emitter, params):
        nowv = jnp.broadcast_to(now, mask.shape).astype(jnp.int64)
        return self._ring_append(state, "c_", mask, {
            "time": nowv,
            "slot": slot.astype(jnp.int32),
            "reset": jnp.ones(mask.shape, bool),
        })

    def _on_tcp_closed(self, state, mask, slot, now, emitter, params):
        nowv = jnp.broadcast_to(now, mask.shape).astype(jnp.int64)
        return self._ring_append(state, "c_", mask, {
            "time": nowv,
            "slot": slot.astype(jnp.int32),
            "reset": jnp.zeros(mask.shape, bool),
        })

    # ------------------------------------------------------------------
    # host-side API (called by ProcessDriver)
    # ------------------------------------------------------------------

    def bind(self, host: int, port: int) -> bool:
        """Bind (host, port) in the device UDP socket table (host-side array
        update; runs between device dispatches)."""
        if (host, port) in self._port_slot:
            return True
        used = np.asarray(jax.device_get(self.sim.state.subs[udp.SUB].used[host]))
        free = np.where(~used)[0]
        if len(free) == 0:
            return False
        slot = int(free[0])
        self._port_slot[(host, port)] = slot
        self.sim.state = self.sim.state.with_sub(
            udp.SUB,
            udp.bind_static(self.sim.state.subs[udp.SUB], host, slot, port),
        )
        return True

    def unbind(self, host: int, port: int) -> None:
        slot = self._port_slot.pop((host, port), None)
        if slot is None:
            return
        u = self.sim.state.subs[udp.SUB]
        self.sim.state = self.sim.state.with_sub(
            udp.SUB, u.replace(used=u.used.at[host, slot].set(False))
        )

    def send(self, t: int, src_host: int, dst_host: int, src_port: int,
             dst_port: int, data: bytes) -> None:
        handle = self._next_handle
        self._next_handle += 1
        self._handles[handle] = data
        self._inflight += 1
        row = np.zeros(pkt.PAYLOAD_WORDS, np.int32)
        row[pkt.W_PROTO] = OP_UDP
        row[pkt.W_SRC_PORT] = src_port
        row[pkt.W_DST_PORT] = dst_port
        row[pkt.W_LEN] = len(data)
        row[pkt.W_SRC_HOST] = src_host
        row[pkt.W_SOCKET] = self._port_slot.get((src_host, src_port), 0)
        row[pkt.W_SEQ] = dst_host  # dst host rides in the seq word
        row[pkt.W_HANDLE] = handle
        self._pending.append((t, src_host, row))

    def take_payload(self, handle: int) -> bytes:
        return self._handles.pop(handle, b"")

    # ---- TCP control plane ----

    def tcp_alloc_slot(self, host: int) -> int | None:
        """Reserve an active-open/listener slot in the CPU-owned region."""
        if not self._tcp_free[host]:
            return None
        return self._tcp_free[host].pop()

    def tcp_free_slot(self, host: int, slot: int) -> None:
        if slot not in self._tcp_free[host]:  # idempotent
            self._tcp_free[host].append(slot)

    def tcp_release(self, host: int, slot: int) -> None:
        """A connection finished with (host, slot): drop it from the live
        set and, if CPU-owned, return it to the mirror free list. Safe to
        call more than once per occupancy."""
        self._tcp_live.discard((host, slot))
        if slot < self.child_base:
            self.tcp_free_slot(host, slot)

    def tcp_listen(self, host: int, port: int) -> int | None:
        """Install a device-side listener (host-side array update between
        dispatches, like UDP bind). Returns the slot or None if full."""
        slot = self.tcp_alloc_slot(host)
        if slot is None:
            return None
        # listeners are deliberately NOT in _tcp_live: a bare listener
        # cannot produce device output without a connect injection first,
        # so it must not defeat sync()'s idle early-out
        self.sim.state = self.sim.state.with_sub(
            tcp_mod.SUB,
            tcp_mod.listen_static(
                self.sim.state.subs[tcp_mod.SUB], host, slot, port
            ),
        )
        return slot

    def tcp_unlisten(self, host: int, slot: int) -> None:
        t = self.sim.state.subs[tcp_mod.SUB]
        self.sim.state = self.sim.state.with_sub(
            tcp_mod.SUB,
            t.replace(
                used=t.used.at[host, slot].set(False),
                state=t.state.at[host, slot].set(tcp_mod.CLOSED),
            ),
        )
        self.tcp_free_slot(host, slot)

    def _tcp_ctl(self, t: int, host: int, op: int, slot: int,
                 words: dict | None = None) -> None:
        row = np.zeros(pkt.PAYLOAD_WORDS, np.int32)
        row[pkt.W_PROTO] = op
        row[pkt.W_SOCKET] = slot
        for w, v in (words or {}).items():
            row[w] = v
        self._pending.append((t, host, row))

    def tcp_connect(self, t: int, src_host: int, slot: int, dst_host: int,
                    dst_port: int, local_port: int) -> None:
        self._tcp_live.add((src_host, slot))
        self._tcp_ctl(
            t, src_host, OP_TCP_CONNECT, slot,
            {pkt.W_SEQ: dst_host, pkt.W_DST_PORT: dst_port,
             pkt.W_SRC_PORT: local_port},
        )

    def tcp_send(self, t: int, host: int, slot: int, nbytes: int) -> None:
        self._tcp_ctl(t, host, OP_TCP_SEND, slot, {pkt.W_LEN: nbytes})

    def tcp_close(self, t: int, host: int, slot: int) -> None:
        self._tcp_ctl(t, host, OP_TCP_CLOSE, slot)

    # ------------------------------------------------------------------
    # injection + drain
    # ------------------------------------------------------------------

    def _inject_pending(self) -> None:
        if not self._pending:
            return
        self._drained = False
        rows = self._pending
        self._pending = []
        pool = self.sim.state.pool
        time_np = np.asarray(jax.device_get(pool.time))
        free = np.where(time_np == NEVER)[0]
        if len(free) < len(rows):
            raise RuntimeError(
                "bridge event pool full (raise event_capacity)"
            )
        idx = jnp.asarray(free[: len(rows)], jnp.int32)
        t = jnp.asarray([r[0] for r in rows], jnp.int64)
        src = jnp.asarray([r[1] for r in rows], jnp.int32)
        payload_rows = np.stack([r[2] for r in rows])
        seq0 = self.sim.state.host.seq_next  # per-src sequence numbers
        seqs = []
        seq_np = np.array(jax.device_get(seq0))  # writable copy
        for (_, s, _row) in rows:
            seqs.append(int(seq_np[s]))
            seq_np[s] += 1
        self.sim.state = self.sim.state.replace(
            pool=pool.replace(
                time=pool.time.at[idx].set(t),
                dst=pool.dst.at[idx].set(src),  # inject AT the sender
                src=pool.src.at[idx].set(src),
                seq=pool.seq.at[idx].set(jnp.asarray(seqs, jnp.int32)),
                kind=pool.kind.at[idx].set(KIND_PROC_SYSCALL),
                payload=pool.payload.at[idx].set(
                    soa.pack_words(jnp.asarray(payload_rows, jnp.int32))
                ),
            ),
            host=self.sim.state.host.replace(
                seq_next=jnp.asarray(seq_np)
            ),
        )

    def _ring_fields(self, prefix: str) -> list[str]:
        """Column names of one ring, derived from the sub-state keys so the
        drain can never silently miss a column added to the schema above.
        A key belongs to ring `prefix` iff it starts with it, the remainder
        has no further ring prefix, and it isn't the count/overflow scalar."""
        br = self.sim.state.subs[BRIDGE_SUB]
        others = [p for p in self._ring_prefixes if p]
        out = []
        for k in br:
            if not k.startswith(prefix):
                continue
            rest = k[len(prefix):]
            if prefix == "" and any(k.startswith(o) for o in others):
                continue
            if rest in ("count", "overflow"):
                continue
            out.append(rest)
        return out

    def _drain_ring(self) -> list:
        # Count-first drain: fetch only the [H] per-ring counts (one small
        # transfer), then fetch ring columns SLICED to the max occupancy of
        # rings that actually hold rows. The old whole-sub device_get moved
        # H*R*~20 arrays device-to-host every window — megabytes per
        # round trip at 1k hosts — for a usually-empty ring.
        br_state = self.sim.state.subs[BRIDGE_SUB]
        fetched = jax.device_get(
            {
                **{p: br_state[f"{p}count"] for p in self._ring_prefixes},
                "_overflow": br_state["overflow"],
            }
        )
        overflow_now = int(np.asarray(fetched.pop("_overflow")))
        counts = {p: np.asarray(v) for p, v in fetched.items()}
        fetch = {}
        for p in self._ring_prefixes:
            cm = int(counts[p].max()) if counts[p].size else 0
            if cm == 0:
                continue
            for name in self._ring_fields(p):
                fetch[f"{p}{name}"] = br_state[f"{p}{name}"][:, :cm]
        if not fetch:
            return []
        br = jax.device_get(fetch)
        out: list = []
        cnt = counts[""]
        if "time" in br:
            for h in np.where(cnt > 0)[0]:
                for c in range(cnt[h]):
                    out.append(Delivery(
                        time=int(br["time"][h, c]),
                        dst_host=int(h),
                        src_host=int(br["src_host"][h, c]),
                        src_port=int(br["src_port"][h, c]),
                        dst_port=int(br["dst_port"][h, c]),
                        length=int(br["length"][h, c]),
                        handle=int(br["handle"][h, c]),
                    ))
        ndel = len(out)
        if self.with_tcp:
            if "e_time" in br:
                ec = counts["e_"]
                for h in np.where(ec > 0)[0]:
                    for c in range(ec[h]):
                        out.append(TcpEstablished(
                            time=int(br["e_time"][h, c]), host=int(h),
                            slot=int(br["e_slot"][h, c]),
                            peer_host=int(br["e_peer_host"][h, c]),
                            peer_port=int(br["e_peer_port"][h, c]),
                            local_port=int(br["e_local_port"][h, c]),
                            is_accept=bool(br["e_accept"][h, c]),
                        ))
            if "r_time" in br:
                rc = counts["r_"]
                for h in np.where(rc > 0)[0]:
                    for c in range(rc[h]):
                        out.append(TcpBytes(
                            time=int(br["r_time"][h, c]), host=int(h),
                            slot=int(br["r_slot"][h, c]),
                            nbytes=int(br["r_bytes"][h, c]),
                        ))
            if "f_time" in br:
                fc = counts["f_"]
                for h in np.where(fc > 0)[0]:
                    for c in range(fc[h]):
                        out.append(TcpFin(
                            time=int(br["f_time"][h, c]), host=int(h),
                            slot=int(br["f_slot"][h, c]),
                            time_wait=bool(br["f_tw"][h, c]),
                        ))
            if "c_time" in br:
                cc = counts["c_"]
                for h in np.where(cc > 0)[0]:
                    for c in range(cc[h]):
                        out.append(TcpClosed(
                            time=int(br["c_time"][h, c]), host=int(h),
                            slot=int(br["c_slot"][h, c]),
                            reset=bool(br["c_reset"][h, c]),
                        ))
        if not out:
            return []
        # reset all rings
        live = self.sim.state.subs[BRIDGE_SUB]
        reset = dict(live)
        for prefix in ("", "e_", "r_", "f_", "c_"):
            if f"{prefix}count" not in reset:
                continue
            reset[f"{prefix}time"] = jnp.full(
                (self.H, self.R), NEVER, jnp.int64
            )
            reset[f"{prefix}count"] = jnp.zeros((self.H,), jnp.int32)
        self.sim.state = self.sim.state.with_sub(BRIDGE_SUB, reset)
        self._inflight = max(0, self._inflight - ndel)
        overflow = overflow_now
        if overflow > self._overflow_seen:
            from shadow_tpu.utils import log

            log.logger.warning(
                "device output ring overflowed %d row(s); raise the bridge "
                "ring_slots / lower events_per_host_per_window",
                overflow - self._overflow_seen,
            )
            self._overflow_seen = overflow
        out.sort(key=lambda d: (
            d.time, _EVENT_RANK[type(d)],
            getattr(d, "dst_host", getattr(d, "host", 0)),
            getattr(d, "slot", getattr(d, "handle", 0)),
        ))
        # Liveness bookkeeping at drain time in device-event order: accepted
        # children become live; slot release (live-set removal + mirror
        # free) is driven by the ProcessDriver via tcp_release, which also
        # guards against stale rows for recycled slots.
        for ev in out:
            if isinstance(ev, TcpEstablished) and ev.is_accept:
                self._tcp_live.add((ev.host, ev.slot))
        return out

    def sync(self, horizon: int) -> list:
        """Flush pending injections and advance the device until the first
        outputs land or its pool drains up to `horizon`. Returns the output
        events (possibly empty)."""
        if not self._pending and (
            self._drained or (self._inflight == 0 and not self._tcp_live)
        ):
            # nothing new injected and the device pool was already observed
            # empty (or nothing is in flight at all): skip the round trip
            return []
        self._inject_pending()
        evs = self._drain_ring()
        if evs:
            return evs
        hz = min(horizon, self.sim.stop_time)
        while True:
            # ONE dispatch advances up to _sync_max_windows windows, exiting
            # early when output lands (fused while_loop — the per-window
            # dispatch + readback round trips were the managed plane's
            # dominant wall cost at 1k processes)
            self.sim.state, mn, nout = self._run_sync(
                self.sim.state, self.sim.params, hz, self._sync_max_windows
            )
            if int(nout):
                evs = self._drain_ring()
                if evs:
                    return evs
            min_next = int(mn)
            if min_next >= NEVER:
                # device fully drained: any UDP datagram still unaccounted
                # was dropped on-device (loss/CoDel/no-socket) — reclaim its
                # payload bytes and the in-flight count
                self._inflight = 0
                self._handles.clear()
                self._drained = True
                return []
            if min_next >= hz:
                return []
