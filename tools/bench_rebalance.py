"""Skew-recovery benchmark (VERDICT r4 gate 3): deliberately skewed PHOLD
(hot 10% of hosts, clustered in shard 0's block by construction) run on the
islands engine with STATIC host→shard assignment vs with the between-window
REBALANCER — the P3 work-stealing replacement
(scheduler_policy_host_steal.c analog).

Static assignment parks every hot host on shard 0: its pool saturates, the
driver's spill tier thrashes host round-trips, and windows clamp below
spilled timestamps. The rebalancer spreads hot hosts across shards and the
run stays on the fast path. Gate: rebalanced >= 1.5x static throughput.

Usage: python tools/bench_rebalance.py [--hosts 4096] [--shards 8]
Prints one JSON line, stamped with the platform it ran on. Run it on the
chip; --cpu forces the CPU backend for a functional check, whose timings
say nothing about the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def build(hosts, shards, rebalance, capacity, msgload, stop_s):
    from shadow_tpu.flagship import SELF_LOOP_50MS_GML
    from shadow_tpu.sim import build_simulation

    return build_simulation({
        "general": {"stop_time": stop_s, "seed": 3},
        "network": {"graph": {"type": "gml", "inline": SELF_LOOP_50MS_GML}},
        "experimental": {
            "event_capacity": capacity,
            "events_per_host_per_window": msgload + 12,
            "outbox_slots": msgload + 12,
            "inbox_slots": 4,
            "num_shards": shards,
            "exchange_slots": max(64, 2 * hosts * msgload // (shards * shards)),
            "rebalance": rebalance,
        },
        "hosts": {"peer": {"quantity": hosts, "app_model": "phold",
                           "app_options": {"msgload": msgload,
                                           "runtime": stop_s - 1,
                                           "hot_frac": 0.1,
                                           "hot_share": 0.6}}},
    })


def timed(sim, stop_s, wpd):
    import jax

    sim.run(until=1_200_000_000, windows_per_dispatch=wpd)  # warm compile
    jax.block_until_ready(sim.state.pool.time)
    t0 = time.perf_counter()
    sim.run(windows_per_dispatch=wpd)
    jax.block_until_ready(sim.state.pool.time)
    wall = time.perf_counter() - t0
    c = sim.counters()
    return wall, c


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=4096)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--msgload", type=int, default=4)
    ap.add_argument("--stop", type=int, default=6)
    ap.add_argument("--wpd", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend")
    args = ap.parse_args()
    if args.cpu:
        from shadow_tpu.parallel.virtualize import force_cpu_devices

        force_cpu_devices(1)
    # Capacity chosen so the hot shard (60% of the population) exceeds its
    # per-shard pool while the BALANCED layout fits comfortably.
    pop = args.hosts * args.msgload
    capacity = args.capacity or int(1.25 * pop)

    st_sim = build(args.hosts, args.shards, False, capacity, args.msgload,
                   args.stop)
    st_wall, st_c = timed(st_sim, args.stop, args.wpd)
    rb_sim = build(args.hosts, args.shards, True, capacity, args.msgload,
                   args.stop)
    rb_wall, rb_c = timed(rb_sim, args.stop, args.wpd)

    assert st_c["events_committed"] == rb_c["events_committed"], (
        st_c["events_committed"], rb_c["events_committed"]
    )
    recovery = st_wall / rb_wall if rb_wall > 0 else 0.0
    print(json.dumps({
        "metric": "skew_recovery_rebalance_vs_static",
        "value": round(recovery, 3),
        "unit": "x",
        "vs_baseline": round(recovery, 3),
        "detail": {
            "platform": jax.devices()[0].platform,
            "hosts": args.hosts, "shards": args.shards,
            "events": st_c["events_committed"],
            "static_wall_s": round(st_wall, 3),
            "rebalanced_wall_s": round(rb_wall, 3),
            "rebalances": rb_sim.rebalances,
            "static_spill_episodes": st_sim.spill_stats()["spill_episodes"],
            "rebalanced_spill_episodes": (
                rb_sim.spill_stats()["spill_episodes"]
            ),
        },
    }))


if __name__ == "__main__":
    main()
