"""Simulated packet representation: payload-word layout.

The reference's Packet (src/main/routing/packet.c:37-77) carries protocol
headers (local/UDP/TCP with seq/ack/window/SACK), payload bytes, an app
priority used by the FIFO qdisc, and a delivery-status trail. On device a
packet is PAYLOAD_WORDS int32 words riding inside an event row; actual
payload BYTES are never materialized on device — only lengths (for device
apps) or CPU-side buffer handles (for managed processes).
"""

from __future__ import annotations

import jax.numpy as jnp

from shadow_tpu.core.state import PAYLOAD_WORDS

# word indices
W_PROTO = 0  # 17 = UDP, 6 = TCP
W_SRC_PORT = 1
W_DST_PORT = 2
W_LEN = 3  # payload bytes
W_PRIORITY = 4  # app-order priority (qdisc FIFO key, packet.c priority)
W_FLAGS = 5  # TCP flags
W_SEQ = 6  # TCP sequence number
W_ACK = 7  # TCP acknowledgment
W_WND = 8  # TCP advertised window
W_SRC_HOST = 9  # global host index of the original sender
W_SOCKET = 10  # sender-side socket slot (for completions)
W_HANDLE = 11  # CPU-side payload buffer handle (managed processes)
# Pure TCP ACKs (len 0, no SYN/FIN) carry a 32-chunk SACK bitmap in the
# handle word (unused there): bit k = receiver holds chunk
# [rcv_nxt + k*MSS, +(k+1)*MSS). The bounded form of the reference's SACK
# ranges (tcp.h:145,171 + tcp_retransmit_tally.cc interval lists).
W_SACK = W_HANDLE

# ---------------------------------------------------------------------------
# Per-packet delivery-status breadcrumb trail (reference packet.c:37-77
# PDS_* trail — its debugging workhorse). Debug mode: simulations built
# with experimental.packet_trails carry ONE EXTRA payload word (index 12)
# into which each stage shifts a 4-bit status code, preserving order —
# up to 8 hops, enough for the longest stage chain. Zero cost when off:
# the word (and every stamp) only exists at payload width >= 13.
# ---------------------------------------------------------------------------
W_TRAIL = 12
TRAILED_PAYLOAD_WORDS = 13

PDS_CREATED = 1
PDS_NIC_QUEUED = 2  # send-ring enqueue (throttled path)
PDS_SENT = 3  # left the NIC onto the wire
PDS_DROPPED_LOSS = 4  # path reliability roll failed (worker.c:539)
PDS_ROUTER_ENQUEUED = 5  # entered the upstream router (router.c:103)
PDS_DROPPED_CODEL = 6  # CoDel control-law drop
PDS_DROPPED_OVERFLOW = 7  # router ring overflow (drop-tail)
PDS_DELIVERED = 8  # reached the destination socket
PDS_DROPPED_SENDQ = 9  # NIC send-ring overflow

PDS_NAMES = {
    PDS_CREATED: "CREATED",
    PDS_NIC_QUEUED: "NIC_QUEUED",
    PDS_SENT: "SENT",
    PDS_DROPPED_LOSS: "DROPPED_LOSS",
    PDS_ROUTER_ENQUEUED: "ROUTER_ENQUEUED",
    PDS_DROPPED_CODEL: "DROPPED_CODEL",
    PDS_DROPPED_OVERFLOW: "DROPPED_OVERFLOW",
    PDS_DELIVERED: "DELIVERED",
    PDS_DROPPED_SENDQ: "DROPPED_SENDQ",
}


def stamp(payload, mask, code):
    """Shift status `code` into masked packets' trail word; no-op when the
    simulation was built without trails (payload width < 13)."""
    if payload.shape[-1] <= W_TRAIL:
        return payload
    tr = payload[..., W_TRAIL]
    new = (tr << 4) | jnp.int32(code)
    if mask.ndim == tr.ndim:
        m = mask
    else:
        m = jnp.broadcast_to(mask, tr.shape)
    return payload.at[..., W_TRAIL].set(jnp.where(m, new, tr))


def decode_trail(word: int) -> list[str]:
    """Trail word → ordered status names (oldest first)."""
    out = []
    w = int(word) & 0xFFFFFFFF
    while w:
        out.append(PDS_NAMES.get(w & 0xF, f"?{w & 0xF}"))
        w >>= 4
    return list(reversed(out))

PROTO_UDP = 17
PROTO_TCP = 6

# header sizes (IPv4 20 + UDP 8 / TCP 20), matching the reference's
# packet_getHeaderSize accounting.
UDP_HEADER_BYTES = 28
TCP_HEADER_BYTES = 40
MTU = 1500  # CONFIG_MTU


def header_bytes(proto):
    return jnp.where(proto == PROTO_TCP, TCP_HEADER_BYTES, UDP_HEADER_BYTES)


def total_bytes(payload):
    """Wire size of a packet given its payload words [...,P]."""
    return payload[..., W_LEN] + header_bytes(payload[..., W_PROTO])


def pack_time(payload, t):
    """Stash an int64 timestamp in the (UDP-unused) seq/ack words."""
    lo = (t & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32)
    hi = (t >> 32).astype(jnp.int32)
    return payload.at[:, W_SEQ].set(lo).at[:, W_ACK].set(hi)


def unpack_time(payload):
    lo = payload[:, W_SEQ].astype(jnp.int64) & 0xFFFFFFFF
    hi = payload[:, W_ACK].astype(jnp.int64)
    return (hi << 32) | lo


def make_udp(src_port, dst_port, length, priority, src_host, socket_slot=None,
             payload_words: int = PAYLOAD_WORDS):
    """Assemble [H, P] payload words for a UDP datagram (vectorized)."""
    words = {
        W_PROTO: PROTO_UDP, W_SRC_PORT: src_port, W_DST_PORT: dst_port,
        W_LEN: length, W_PRIORITY: priority, W_SRC_HOST: src_host,
    }
    if payload_words > W_TRAIL:
        words[W_TRAIL] = PDS_CREATED
    if socket_slot is not None:
        words[W_SOCKET] = socket_slot
    return stack_words(words, src_port.shape[0], payload_words)


def stack_words(words: dict, H: int, payload_words: int):
    """[H, payload_words] int32 payload from {word index: [H] or scalar},
    zero elsewhere — built by stacking columns, not by per-column .at[]
    updates, which lower to scatters."""
    zero = jnp.zeros((H,), jnp.int32)
    return jnp.stack(
        [
            jnp.broadcast_to(jnp.asarray(words[w], jnp.int32), (H,))
            if w in words else zero
            for w in range(payload_words)
        ],
        axis=1,
    )
