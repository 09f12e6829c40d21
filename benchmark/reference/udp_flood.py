"""Plain reference of the UDP flood (app model `udp_flood`): iperf-style
clients send one datagram every `interval` to a server, past the server's
downlink, through Shadow's upstream-router CoDel queue.

Written from the semantics, not from the program:

- Hosts are numbered in name order (common.hosts). Client h sends to
  server number h mod S (round robin over host numbers; `local_span` 0).
- Client j (in host order) of the n clients starts at start_time +
  slot_j * start_spread // n. The slots are a permutation of 0..n-1,
  stratified by server: each server's m clients (in host order, servers
  in order) draw keys (permutation(m) + random(m)) / m from one numpy
  default_rng(seed mod 2^64), and a slot is a client's rank by key. Each
  client then sends every `interval` while the send time is before
  start_time + runtime. Each
  send takes the client's uplink at once (the reference checks that its
  bucket always admits) and arrives one path latency later; the graph
  loses nothing.
- A server's downlink is a token bucket on a 1 ms grid: at each grid tick
  it refills by bandwidth x 1 ms, capped at that plus one MTU (1500 B); a
  packet needs MTU tokens and is charged its wire size (payload + 28 B of
  IP and UDP header).
- An arrival finds the router queue empty and the bucket full enough: it
  is delivered at once (CoDel's interval and drop mode reset, as a packet
  of no sojourn would). Otherwise it joins the queue, if the queue has
  room (its depth: the host's `router_queue_slots`, else the
  experimental one), and arms the receive pump at its own time unless
  the pump is armed.
- The receive pump, while tokens allow, makes one CoDel dequeue and
  delivers what it returns; it re-arms itself at the same time while
  tokens remain and the queue is not empty, else at the next grid tick.
- CoDel is Shadow's (router_queue_codel.c: target 10 ms, interval
  100 ms) with the control law of RFC 8289, next = t + interval /
  sqrt(count) rounded to the nanosecond, and at most one drop per
  dequeue besides the drop that enters drop mode.

Events at one host run in (time, source host, sequence) order: an
arrival from a lower-numbered host runs before a pump armed for the same
time. Each server is independent once its arrivals are known, so the
reference replays each one in order of events, in a `lax.while_loop` on
the host's CPU, to the frontier.
"""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.spec import load_module

# this tree's common.py, as Spec.reference loads this file by its path
common = load_module(Path(__file__).with_name("common.py"))

# where the program under test keeps what the reference predicts
FIELDS = {
    "sent": "subs.udp_flood.sent",
    "recv": "subs.udp_flood.recv",
    "queue": "subs.router.q_tail - subs.router.q_head",
    "drop_count": "subs.router.drop_count",
}
TOTALS = {
    "packets_sent": "counters.packets_sent",
    "packets_delivered": "counters.packets_delivered",
    "codel_dropped": "subs.router.codel_dropped",
}
# the router rings' and the NIC send rings' drops for want of room
CAPACITY = ("subs.router.overflow_dropped", "subs.nic.sendq_dropped")

MS = 10**6
MTU = 1500
HEADER = 28  # IPv4 20 + UDP 8
TARGET = 10 * MS
INTERVAL = 100 * MS
INF = np.iinfo(np.int64).max
# Shadow's built-in graph: one vertex, 1 Gbit/s each way, 1 ms self-loop
ONE_GBIT_SWITCH = {"latency_ns": MS, "loss": 0.0, "bw_up": 10**9,
                   "bw_down": 10**9}


def _link(cfg: dict) -> dict:
    if cfg["network"]["graph"].get("type", "gml") == "1_gbit_switch":
        return dict(ONE_GBIT_SWITCH)
    return common.self_loop(cfg)


def _interval_ns(value) -> int:
    # a bare number is milliseconds for the flood's interval
    return int(value * MS) if isinstance(value, (int, float)) \
        else common.time_ns(value)


def _refill(bits_per_s: int) -> int:
    """Bytes a bucket gains per 1 ms tick."""
    return max(bits_per_s // 8 * MS // 10**9, 1)


def _control_law(count, ts):
    return ts + jnp.round(
        INTERVAL / jnp.sqrt(jnp.maximum(count, 1).astype(jnp.float64))
    ).astype(jnp.int64)


def _pop(s, front, now, want):
    """One pop of the queue's head where `want`; returns the state, whether
    a packet came out, and whether CoDel may drop it (sojourn above the
    target for a whole interval). `front` holds the head's position and the
    enqueue times of the queue's first three packets, all that one dequeue
    can pop."""
    head, count = s["head"], s["count"]
    have = want & (count > 0)
    enq = front[1][head - front[0]]
    total = jnp.where(have, s["total"] - s["size"], s["total"])
    good = (now - enq < TARGET) | (total < MTU)
    ie = s["ie"]
    ok = have & ~good & (ie != 0) & (now >= ie)
    new_ie = jnp.where(have & good, 0, ie)
    new_ie = jnp.where(have & ~good & (ie == 0), now + INTERVAL, new_ie)
    new_ie = jnp.where(want & (count == 0), 0, new_ie)
    s = dict(s, head=head + have, count=count - have, total=total, ie=new_ie)
    return s, have, ok


def _dequeue(s, front, now, want):
    """One CoDel dequeue where `want`; returns the state and whether a
    packet is delivered."""
    s, have, ok = _pop(s, front, now, want)
    mode = jnp.where(want & ~have, False, s["mode"])
    mode = jnp.where(want & have & mode & ~ok, False, mode)
    # drop mode: drop the packet in hand once its time has come
    drop = want & have & mode & (now >= s["next_drop"])
    count = s["drop_count"] + drop
    s = dict(s, drops=s["drops"] + drop, drop_count=count)
    s, have2, ok2 = _pop(s, front, now, drop)
    have = jnp.where(drop, have2, have)
    ok = jnp.where(drop, ok2, ok)
    next_drop = jnp.where(drop & ok2, _control_law(count, s["next_drop"]),
                          s["next_drop"])
    mode = jnp.where(drop & ~ok2, False, mode)
    # store mode, and the packet in hand may drop: drop it, enter drop mode
    enter = want & have & ~mode & ok
    s = dict(s, drops=s["drops"] + enter)
    s, have3, _ = _pop(s, front, now, enter)
    have = jnp.where(enter, have3, have)
    delta = count - s["last_count"]
    recently = now < next_drop + 16 * INTERVAL
    new = jnp.where(recently & (delta > 1), delta, 1)
    return dict(
        s,
        mode=mode | enter,
        drop_count=jnp.where(enter, new, count),
        next_drop=jnp.where(enter, _control_law(new, now), next_drop),
        last_count=jnp.where(enter, new, s["last_count"]),
    ), have


def _arrival(s, front, now):
    """An arrival: straight through when nothing waits and tokens allow,
    else into the queue if it has room (whether it went in is returned),
    arming the pump."""
    direct = (s["count"] == 0) & (s["rem"] >= MTU)
    room = s["count"] < s["depth"]
    put = ~direct & room
    arm = ~direct & ~s["armed"]
    return dict(
        s, i=s["i"] + 1,
        rem=s["rem"] - jnp.where(direct, s["size"], 0),
        count=s["count"] + put,
        total=s["total"] + jnp.where(put, s["size"], 0),
        overflow=s["overflow"] + (~direct & ~room),
        ie=jnp.where(direct, 0, s["ie"]),
        mode=s["mode"] & ~direct,
        armed=s["armed"] | arm,
        pump_t=jnp.where(arm, now, s["pump_t"]),
        recv=s["recv"] + direct,
    ), put


def _pump(s, front, now):
    """The receive pump: one dequeue while tokens allow, then re-arm while
    the queue holds packets, at once or at the next grid tick."""
    s, have = _dequeue(s, front, now, s["rem"] >= MTU)
    rem = s["rem"] - jnp.where(have, s["size"], 0)
    rearm = s["count"] > 0
    return dict(
        s, rem=rem, armed=rearm, recv=s["recv"] + have,
        pump_t=jnp.where(rearm, jnp.where(rem >= MTU, now,
                                          (now // MS + 1) * MS),
                         s["pump_t"]),
    ), jnp.bool_(False)


def _server_step(carry, arr_t, arr_src, frontier):
    """Run the server's next event before the frontier: its next arrival
    or its pump, whichever comes first in (time, source) order. The queue's
    ring of enqueue times rides beside the state and is written here, in
    place."""
    s, ring = carry
    a_t, a_src = arr_t[s["i"]], arr_src[s["i"]]
    pump = jnp.where(s["armed"], s["pump_t"], INF)
    is_arr = (a_t < pump) | ((a_t == pump) & (a_src < s["me"]))
    now = jnp.where(is_arr, a_t, pump)
    run = now < frontier
    # the downlink bucket catches up with the grid
    tick = now // MS
    later = run & (tick > s["tick"])
    s = dict(s, tick=jnp.where(later, tick, s["tick"]), rem=jnp.where(
        later, jnp.minimum(s["cap"],
                           s["rem"] + (tick - s["tick"]) * s["refill"]),
        s["rem"]))
    R = ring.shape[0]
    slot = (s["head"] + s["count"]) % R
    front = (s["head"], jnp.stack([ring[(s["head"] + k) % R]
                                   for k in range(3)]))
    s, put = jax.lax.cond(
        run, lambda s: jax.lax.cond(is_arr, _arrival, _pump, s, front, now),
        lambda s: (dict(s, done=True), jnp.bool_(False)), s)
    ring = jax.lax.dynamic_update_slice(
        ring, jnp.where(put, now, ring[slot])[None], (slot,))
    return dict(s, peak=jnp.maximum(s["peak"], s["count"])), ring


@functools.partial(jax.jit, static_argnums=(5,))
def _replay(arr_t, arr_src, me, depth, link, ring_slots, frontier):
    """One server's state once each of its events before `frontier` has
    run. Its queue is a ring of enqueue times, written in place."""
    z = jnp.int64(0)
    s = {
        "i": z, "me": me, "depth": depth, "head": z, "count": z, "total": z, "size": link["size"],
        "rem": link["cap"], "tick": z, "cap": link["cap"],
        "refill": link["refill"],
        "armed": jnp.bool_(False), "pump_t": z,
        "mode": jnp.bool_(False), "ie": z, "next_drop": z,
        "drop_count": z, "last_count": z,
        "recv": z, "drops": z, "overflow": z, "peak": z,
        "done": jnp.bool_(False),
    }
    s, _ = jax.lax.while_loop(
        lambda c: ~c[0]["done"],
        lambda c: _server_step(c, arr_t, arr_src, frontier),
        (s, jnp.zeros((ring_slots,), jnp.int64)))
    return {k: s[k] for k in ("recv", "count", "drop_count", "drops",
                              "overflow", "armed", "peak")}


def _host_device():
    """The CPU, where the replay's one-at-a-time steps and float64 run
    best, if the process has it."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def _slots(target: np.ndarray, seed: int) -> np.ndarray:
    """Each client's start slot, 0..n-1: for each server in order, its m
    clients draw keys (permutation(m) + random(m)) / m from one
    default_rng(seed mod 2^64); a slot is a client's rank by key."""
    rng = np.random.default_rng(int(seed) % 2**64)
    key = np.empty(len(target))
    for t in np.unique(target):
        mine = np.flatnonzero(target == t)
        key[mine] = (rng.permutation(len(mine))
                     + rng.random(len(mine))) / len(mine)
    return np.argsort(np.argsort(key, kind="stable"),
                      kind="stable").astype(np.int64)


def flood(cfg: dict, frontier_ns: int, uniform: bool = False) -> dict:
    """Per-host numbers at the frontier, with every server's replay
    (`uniform`: every ring at the experimental depth)."""
    hosts = common.hosts(cfg)
    H = len(hosts)
    link = _link(cfg)
    if link["loss"] > 0:
        raise NotImplementedError("the reference models a lossless graph")
    if common.time_ns(cfg["general"].get("bootstrap_end_time", 0)) > 0:
        raise ValueError("the reference models no bootstrap period")
    exp = cfg.get("experimental", {})
    if exp.get("router_queue_variant", "codel") != "codel":
        raise NotImplementedError("the reference models the CoDel router")
    shallow = int(exp.get("router_queue_slots", 64))
    defaults = cfg.get("host_defaults", {})

    def opt(block, key):
        return block.get(key, defaults.get(key))

    servers = [h for h, (_, b) in enumerate(hosts)
               if b["app_options"].get("role", "client") == "server"]
    clients = [h for h in range(H) if h not in set(servers)]
    if len({str(sorted(hosts[h][1]["app_options"].items()))
            for h in clients}) != 1:
        raise ValueError("the flood's clients share one set of options")
    copts = hosts[clients[0]][1]["app_options"]
    if int(copts.get("local_span", 0)):
        raise NotImplementedError("the reference models round-robin targets")
    interval = _interval_ns(copts.get("interval", 100))
    size = int(copts.get("size", 1024)) + HEADER
    start = common.time_ns(copts.get("start_time", 1))
    spread = common.time_ns(copts.get("start_spread", 0))
    stop = (start + common.time_ns(copts["runtime"])
            if "runtime" in copts else INF)

    # clients: a timer every interval from each start, while before stop
    target = np.asarray(servers)[np.arange(H) % len(servers)]
    n = len(clients)
    t0 = np.full(H, INF, np.int64)
    t0[clients] = start + _slots(target[clients], cfg["general"]["seed"]) \
        * spread // n if spread else start
    end = min(frontier_ns, stop)
    sent = np.zeros(H, np.int64)
    sends = np.maximum(0, -(-(end - t0[clients]) // interval))
    sent[clients] = sends
    pending = np.zeros(H, np.int64)
    # the timer due next is pending unless the last one that ran stopped
    nxt = np.maximum(0, -(-(frontier_ns - t0[clients]) // interval))
    pending[clients] = (nxt == 0) | (t0[clients] + (nxt - 1) * interval
                                     < stop)
    for h in clients:
        up = _refill(common.bits(opt(hosts[h][1], "bandwidth_up"))
                     if opt(hosts[h][1], "bandwidth_up") else link["bw_up"])
        if up < MTU or interval < MS:
            raise NotImplementedError(
                "the reference models clients whose uplink never queues")

    # servers: their arrivals, then each one's replay
    lat = link["latency_ns"]
    per = {s: ([np.zeros(0, np.int64)], [np.zeros(0, np.int64)])
           for s in servers}
    for h, k in zip(clients, sends):
        t = t0[h] + interval * np.arange(k, dtype=np.int64) + lat
        per[target[h]][0].append(t)
        per[target[h]][1].append(np.full(k, h, np.int64))
    arrivals = [(np.concatenate(per[s][0]), np.concatenate(per[s][1]))
                for s in servers]
    N = max(len(t) for t, _ in arrivals) + 1
    arr_t = np.full((len(servers), N), INF, np.int64)
    arr_src = np.zeros((len(servers), N), np.int64)
    for j, (t, src) in enumerate(arrivals):
        order = np.lexsort((src, t))
        arr_t[j, :len(t)] = t[order]
        arr_src[j, :len(t)] = src[order]
        # in flight at the frontier: sent before it, arriving at or after
        pending[servers[j]] = int(np.sum(t >= frontier_ns))
    depth = np.array([
        shallow if uniform or opt(hosts[s][1], "router_queue_slots") is None
        else int(opt(hosts[s][1], "router_queue_slots")) for s in servers])
    caps = []
    for s in servers:
        bw = opt(hosts[s][1], "bandwidth_down")
        caps.append(_refill(common.bits(bw) if bw else link["bw_down"]))
    if len(set(caps)) > 1:
        raise NotImplementedError("the reference models one downlink rate")
    refill = caps[0]
    with jax.default_device(_host_device()):
        link = {"size": jnp.int64(size), "cap": jnp.int64(refill + MTU),
                "refill": jnp.int64(refill)}
        runs = [common.as_numpy(_replay(
            jnp.asarray(arr_t[j]), jnp.asarray(arr_src[j]),
            jnp.int64(servers[j]), jnp.int64(depth[j]), link,
            int(depth.max()), jnp.int64(frontier_ns)))
            for j in range(len(servers))]
    out = {k: np.stack([r[k] for r in runs]) for k in runs[0]}
    recv = np.zeros(H, np.int64)
    queue = np.zeros(H, np.int64)
    drop_count = np.zeros(H, np.int64)
    recv[servers] = out["recv"]
    queue[servers] = out["count"]
    drop_count[servers] = out["drop_count"]
    pending[servers] += out["armed"].astype(np.int64)
    return {
        "sent": sent, "recv": recv, "queue": queue,
        "drop_count": drop_count, "pending": pending,
        "packets_sent": int(sent.sum()),
        "packets_delivered": int(recv.sum()),
        "codel_dropped": int(out["drops"].sum()),
        "overflow": int(out["overflow"].sum()),
        "peak": out["peak"], "servers": servers,
    }


def expected(cfg: dict, frontier_ns: int) -> dict:
    """What the program must hold once every event before `frontier_ns`
    is handled and none at or after it, with each host's router ring at
    the depth the configuration gives it."""
    out = flood(cfg, frontier_ns)
    return {k: out[k] for k in (*FIELDS, *TOTALS, "pending")}


def control(cfg: dict, frontier_ns: int) -> dict:
    """The same with every host's ring at the shallow depth
    (experimental.router_queue_slots): the uniform ring a later change
    might be tempted to cut the deep hosts back to. It loses datagrams for
    want of room."""
    out = flood(cfg, frontier_ns, uniform=True)
    return {k: out[k] for k in (*FIELDS, *TOTALS, "pending")}
