"""The flagship on-device workloads.

PHOLD over a 50ms self-loop link is the reference's PDES canary
(src/test/phold/phold.yaml: peers on a single-vertex self-loop graph
exchanging random-destination messages) scaled to arbitrary host counts.
The UDP flood is BASELINE config 2: clients flooding servers through the
full device network stack (NIC token buckets, CoDel router, UDP sockets).
Shared by bench.py, chip_smoke.py and __graft_entry__.py so the benchmark,
the chip smoke and the compile checks always exercise the same models.
"""

from __future__ import annotations

SELF_LOOP_50MS_GML = """\
graph [
  node [ id 0 bandwidth_down "81920 Kibit" bandwidth_up "81920 Kibit" ]
  edge [ source 0 target 0 latency "50 ms" packet_loss 0.0 ]
]
"""


def build_phold_flagship(
    num_hosts: int,
    msgload: int = 2,
    stop_s: int = 10,
    runtime_s: int | None = None,
    event_capacity: int | None = None,
    K: int | None = None,
    seed: int = 42,
    num_shards: int = 1,
    island_mode: str = "vmap",
    exchange_slots: int = 0,
    mesh_exchange: str = "ppermute",
    placement: str = "block",
    obs_counters: bool = True,
    pool_gears: int = 1,
    audit_digest: bool = True,
    flight_recorder: int = 0,
    pipelined_dispatch: bool = True,
    host_workers: int = 1,
):
    from shadow_tpu.sim import build_simulation

    if runtime_s is None:
        runtime_s = max(stop_s - 2, 1)
    if event_capacity is None:
        # PHOLD's live population is num_hosts × msgload messages; the
        # merge only ever holds leftovers + one window's emissions, so
        # 1.5× covers it with headroom (pool_overflow_dropped is asserted
        # zero by the bench). The window sort scales with the pool and the
        # merge sort with pool + H*K, so tight sizing is a direct speedup.
        event_capacity = max(3 * num_hosts * msgload // 2, 4096)
    if K is None:
        # Random destinations make per-host wave occupancy Poisson(msgload);
        # K must cover the max over ALL hosts or straggler hosts defer into
        # an EXTRA whole window pass per wave (correct but ~2x slower —
        # each pass costs the full sort pipeline). msgload + 16 puts the
        # per-wave straggler probability near zero beyond 100k hosts while
        # the [H, K] filler block stays modest.
        K = msgload + 16
    island_exp = {}
    if num_shards > 1:
        if exchange_slots <= 0:
            # PHOLD cross-shard volume per window per destination shard:
            # one wave ≈ Hl·msgload emissions per shard spread uniformly
            # over S destinations. No headroom multiplier: misses defer
            # safely under the window-end clamp, while every extra slot
            # costs S pool rows AND S grouping-sort fillers per shard —
            # oversizing re-grows the sort volume islands exist to shrink
            # (VERDICT r4 weak #1; islands.suggest_exchange_slots() gives
            # the measured-traffic figure for retuning).
            hl = num_hosts // num_shards
            exchange_slots = max(64, hl * msgload // num_shards)
        island_exp = {
            "num_shards": num_shards,
            "island_mode": island_mode,
            "exchange_slots": exchange_slots,
            "mesh_exchange": mesh_exchange,
            "placement": placement,
        }
    return build_simulation(
        {
            "general": {"stop_time": stop_s, "seed": seed},
            "network": {"graph": {"type": "gml", "inline": SELF_LOOP_50MS_GML}},
            "experimental": {
                "event_capacity": event_capacity,
                "events_per_host_per_window": K,
                **island_exp,
                # PHOLD emits exactly one event per handled event, so K
                # outbox slots per host can never overflow; small boxes keep
                # the per-window merge sort lean (the hot cost at scale).
                "outbox_slots": K,
                "inbox_slots": 4,
                "obs_counters": obs_counters,
                "pool_gears": pool_gears,
                "audit_digest": audit_digest,
                "flight_recorder": flight_recorder,
                "pipelined_dispatch": pipelined_dispatch,
                "host_workers": host_workers,
            },
            "hosts": {
                "peer": {
                    "quantity": num_hosts,
                    "app_model": "phold",
                    "app_options": {"msgload": msgload, "runtime": runtime_s},
                }
            },
        }
    )


def stack_config(
    app_model: str,
    num_hosts: int,
    *,
    loss: float,
    app_options: dict,
    stop_s: int = 4,
    seed: int = 7,
    event_capacity: int = 1 << 15,
    experimental: dict | None = None,
) -> dict:
    """Config dict of a network-stack workload: one server per eight
    hosts, the rest clients with `app_options`, every host on one 1 Gbit
    vertex over a 10 ms self-loop with `loss`."""
    n_servers = num_hosts // 8
    return {
        "general": {"stop_time": stop_s, "seed": seed},
        "network": {"graph": {"type": "gml", "inline": (
            'graph [\n'
            '  node [ id 0 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]\n'
            f'  edge [ source 0 target 0 latency "10 ms" packet_loss {loss} ]\n'
            ']\n')}},
        # Pool capacity sized to the workload's in-flight population
        # (timers + packets in transit): the per-window pool sort scales
        # with capacity, so oversizing it is pure waste.
        "experimental": {
            "event_capacity": event_capacity,
            "events_per_host_per_window": 16,
            "outbox_slots": 16,
            # every ring/inbox slot is a full [H, slots, P] write per
            # update, so these are sized to the queue depths, no further
            "router_queue_slots": 16,
            "inbox_slots": 4,
            **(experimental or {}),
        },
        "hosts": {
            "server": {"quantity": n_servers, "app_model": app_model,
                       "app_options": {"role": "server"}},
            "client": {"quantity": num_hosts - n_servers,
                       "app_model": app_model, "app_options": app_options},
        },
    }


def udp_flood_config(num_hosts: int, stop_s: int = 4, **experimental) -> dict:
    """BASELINE config 2 at `num_hosts`: 1 KiB datagrams every 20 ms per
    client. K/O/C are sized to the workload's Poisson tails: 1 << 14 pool
    rows overflow at 10k hosts, 1 << 15 do not."""
    return stack_config(
        "udp_flood", num_hosts, loss=0.001,
        app_options={"interval": "20 ms", "size": 1024,
                     "runtime": stop_s - 1},
        stop_s=stop_s,
        experimental={"events_per_host_per_window": 12, "outbox_slots": 8,
                      **experimental},
    )


def build_udp_flood(num_hosts: int, stop_s: int = 4, **experimental):
    from shadow_tpu.sim import build_simulation

    return build_simulation(
        udp_flood_config(num_hosts, stop_s=stop_s, **experimental)
    )
