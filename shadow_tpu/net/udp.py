"""UDP sockets: per-host socket table, port binding, demux, delivery.

Reference: src/main/host/descriptor/udp.c (straight packet in/out queues
over the Socket base) and the NIC's (proto, port, peer)-keyed binding
hashtable (network_interface.c:391-441) — a general (peer=0) binding catches
server traffic, a peer-specific binding catches connected sockets.

Device form: a fixed [H, S] socket table; demux compares the incoming
packet's (proto, dst_port, src_host, src_port) against all S slots at once;
peer-specific matches outrank general ones. Received datagrams are counted
and handed to the app-receive hook (device apps) or queued for the CPU
syscall plane (managed processes; the recv ring lands with that plane).
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import struct

from shadow_tpu.core import soa
from shadow_tpu.net import packet as pkt

SUB = "udp"

ANY_PEER = -1


@struct.dataclass
class UdpState:
    used: jnp.ndarray  # [H, S] bool
    bind_port: jnp.ndarray  # [H, S] i32
    peer_host: jnp.ndarray  # [H, S] i32 (ANY_PEER = unconnected)
    peer_port: jnp.ndarray  # [H, S] i32
    recv_pkts: jnp.ndarray  # [H, S] i64
    recv_bytes: jnp.ndarray  # [H, S] i64
    sent_pkts: jnp.ndarray  # [H, S] i64
    sent_bytes: jnp.ndarray  # [H, S] i64
    drop_no_socket: jnp.ndarray  # [] i64 (PDS_RCV_INTERFACE_DROPPED analog)


def init(num_hosts: int, sockets_per_host: int = 8) -> UdpState:
    H, S = num_hosts, sockets_per_host
    return UdpState(
        used=jnp.zeros((H, S), bool),
        bind_port=jnp.zeros((H, S), jnp.int32),
        peer_host=jnp.full((H, S), ANY_PEER, jnp.int32),
        peer_port=jnp.zeros((H, S), jnp.int32),
        recv_pkts=jnp.zeros((H, S), jnp.int64),
        recv_bytes=jnp.zeros((H, S), jnp.int64),
        sent_pkts=jnp.zeros((H, S), jnp.int64),
        sent_bytes=jnp.zeros((H, S), jnp.int64),
        drop_no_socket=jnp.zeros((), jnp.int64),
    )


def bind_static(udp: UdpState, host, slot: int, port: int,
                peer_host: int = ANY_PEER, peer_port: int = 0) -> UdpState:
    """Build-time binding (device apps declare their sockets up front).
    `host` is one host or an array of hosts bound alike in one update —
    one eager update per host made a 40k-host build take minutes."""
    return udp.replace(
        used=udp.used.at[host, slot].set(True),
        bind_port=udp.bind_port.at[host, slot].set(port),
        peer_host=udp.peer_host.at[host, slot].set(peer_host),
        peer_port=udp.peer_port.at[host, slot].set(peer_port),
    )


def demux(udp: UdpState, mask, payload, src_host):
    """Find the receiving socket slot per host for an incoming packet.

    Returns (slot [H] i32, found [H] bool); peer-specific beats general,
    lowest slot wins ties (deterministic).
    """
    H, S = udp.used.shape
    dport = payload[:, pkt.W_DST_PORT][:, None]  # [H,1]
    sport = payload[:, pkt.W_SRC_PORT][:, None]
    srch = src_host.astype(jnp.int32)[:, None]
    port_ok = udp.used & (udp.bind_port == dport)
    specific = port_ok & (udp.peer_host == srch) & (udp.peer_port == sport)
    general = port_ok & (udp.peer_host == ANY_PEER)
    # prefer specific: score 2 for specific, 1 for general, 0 none; take the
    # highest-score, lowest-slot match.
    score = specific.astype(jnp.int32) * 2 + general.astype(jnp.int32)
    best = jnp.max(score, axis=1)
    slot = jnp.argmax(score, axis=1).astype(jnp.int32)
    found = mask & (best > 0)
    return slot, found


def deliver(udp: UdpState, mask, slot, payload) -> UdpState:
    """Count a datagram into its socket (the app hook runs separately)."""
    nbytes = payload[:, pkt.W_LEN].astype(jnp.int64)
    return udp.replace(
        recv_pkts=soa.add_at(udp.recv_pkts, mask, slot, 1),
        recv_bytes=soa.add_at(udp.recv_bytes, mask, slot, nbytes),
    )


def count_sent(udp: UdpState, mask, slot, payload) -> UdpState:
    nbytes = payload[:, pkt.W_LEN].astype(jnp.int64)
    return udp.replace(
        sent_pkts=soa.add_at(udp.sent_pkts, mask, slot, 1),
        sent_bytes=soa.add_at(udp.sent_bytes, mask, slot, nbytes),
    )
