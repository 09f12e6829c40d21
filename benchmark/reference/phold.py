"""Plain reference of PHOLD (Shadow's src/test/phold): every host starts
`msgload` messages to itself at `start_time`; each message handled at
time t is forwarded, while t < start_time + runtime, to a uniformly drawn
other host and arrives one path latency later. A forward spends two draws
of the sender's stream, the destination (`me + 1 + floor(u*(H-1))` mod H,
i.e. self skipped) and the loss roll against the path's reliability.

On a one-vertex graph every message moves in lockstep, so the run is a
sequence of waves at start + k*latency. Messages of one wave at one host
are alike, so which of them takes which draw does not matter: the j-th
message a host handles in a wave takes counters c + 2j. The reference
replays the waves up to the frontier and returns what every host must
hold there.

A wave is one sort of the messages by host: a message's rank among its
host's is its index less the start of its host's run (a running
maximum), the hosts' counts are one sum over the sorted messages, and
each message derives its host's key itself rather than gathering it.
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
    "received": "subs.phold.received",
    "forwarded": "subs.phold.forwarded",
    "rng_counter": "host.rng_counter",
}
TOTALS = {
    "events_committed": "counters.events_committed",
    "packets_sent": "counters.packets_sent",
    "packets_dropped_loss": "counters.packets_dropped_loss",
}
# PHOLD has no bounded ring beyond the engine's pool and outbox
CAPACITY = ()


@functools.partial(jax.jit, static_argnums=(0, 10))
def _waves(H, root, dst, counter, received, forwarded, live, n_waves,
           n_send, thresh, dtype):
    N = dst.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    ones = jnp.ones((N,), jnp.int32)

    def wave(k, c):
        dst, counter, received, forwarded, live = c
        d = jnp.sort(jnp.where(live, dst, H))
        counts = jax.ops.segment_sum(ones, d, H + 1, indices_are_sorted=True
                                     )[:H].astype(jnp.int64)
        alive = d < H
        dh = jnp.minimum(d, H - 1)
        # rank of each message among its host's: index less run start
        start = jnp.concatenate([jnp.ones((1,), bool), d[1:] != d[:-1]])
        first = jax.lax.cummax(jnp.where(start, idx, 0), axis=0)
        j = (idx - first).astype(jnp.uint32)
        ctr = counter[dh] + 2 * j
        keys = common.host_keys_of(root, dh)
        u = common.draw(keys, ctr, dtype)
        roll = common.draw(keys, ctr + 1, dtype)
        to = jnp.clip(jnp.floor(u * (H - 1)).astype(jnp.int32), 0, H - 2)
        to = to + (to >= dh)
        send = k < n_send
        sent = jnp.where(send, counts, 0)
        return (
            to,
            counter + 2 * sent.astype(jnp.uint32),
            received + counts,
            forwarded + sent,
            alive & send & (roll < thresh),
        )

    return jax.lax.fori_loop(
        0, n_waves, wave, (dst, counter, received, forwarded, live))


def expected(cfg: dict, frontier_ns: int, dtype=jnp.float32) -> dict:
    """What the program must hold once every event before `frontier_ns`
    is handled and none at or after it."""
    hosts = common.hosts(cfg)
    H = len(hosts)
    opts = hosts[0][1].get("app_options", {})
    msgload = int(opts.get("msgload", 1))
    start = common.time_ns(opts.get("start_time", 1))
    stop_send = start + common.time_ns(opts.get("runtime", 5))
    link = common.self_loop(cfg)
    lat = link["latency_ns"]
    boot = common.time_ns(cfg["general"].get("bootstrap_end_time", 0))
    if boot > start:
        raise ValueError("the reference models no bootstrap period")
    # waves with time < frontier are handled; those before stop_send forward
    n_waves = max(0, -(-(frontier_ns - start) // lat))
    n_send = max(0, -(-(stop_send - start) // lat))
    root = common.root_key(int(cfg["general"]["seed"]))
    dst = jnp.repeat(jnp.arange(H, dtype=jnp.int32), msgload)
    zeros = jnp.zeros((H,), jnp.int64)
    dst, counter, received, forwarded, live = _waves(
        H, root, dst, jnp.zeros((H,), jnp.uint32), zeros, zeros,
        jnp.ones(dst.shape, bool), n_waves, n_send,
        jnp.float32(1.0 - link["loss"]).astype(dtype), dtype)
    out = common.as_numpy({
        "received": received, "forwarded": forwarded, "rng_counter": counter,
    })
    out["pending"] = np.bincount(np.asarray(dst)[np.asarray(live)],
                                 minlength=H)
    out["events_committed"] = int(out["received"].sum())
    out["packets_sent"] = int(out["forwarded"].sum())
    # every forward is handled, pending, or lost to the roll
    out["packets_dropped_loss"] = int(
        out["packets_sent"] + H * msgload - out["events_committed"]
        - out["pending"].sum())
    return out


def control(cfg: dict, frontier_ns: int) -> dict:
    """The reference with its draws (destination, loss roll) in bfloat16,
    the precision below the float32 the program draws in."""
    return expected(cfg, frontier_ns, jnp.bfloat16)
