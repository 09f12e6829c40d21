"""The UDP flood past a server's downlink: the deep router ring on the
fan-in hosts, the staggered client starts, and the program against the
benchmark's plain reference (benchmark/reference/udp_flood.py) at a small
size, through CoDel's drop mode."""

from __future__ import annotations

import copy
from pathlib import Path

import jax
import numpy as np
import pytest

from shadow_tpu.core.config import ConfigError, load_config
from shadow_tpu.sim import BuildError, _start_offsets, build_simulation

ROOT = Path(__file__).resolve().parents[1]
GML = ('graph [\n  node [ id 0 bandwidth_down "1 Gbit" bandwidth_up '
       '"1 Gbit" ]\n  edge [ source 0 target 0 latency "10 ms" '
       'packet_loss 0.0 ]\n]\n')
DEEP = 512
FRONTIER_NS = 1_000_000_000
SEEDS = (3, 2**31 + 11)

# 2 servers whose 1 Mbit downlinks admit 83 datagrams/s, each sent 150/s
# by 15 clients: the queue builds at once and CoDel drops within a second
BASE = {
    "general": {"stop_time": "100 s", "seed": 1},
    "network": {"graph": {"type": "gml", "inline": GML}},
    "experimental": {"event_capacity": 1024, "router_queue_slots": 4,
                     "events_per_host_per_window": 8, "inbox_slots": 4,
                     "outbox_slots": 4},
    "hosts": {
        "server": {"quantity": 2, "app_model": "udp_flood",
                   "bandwidth_down": "1 Mbit", "router_queue_slots": DEEP,
                   "app_options": {"role": "server"}},
        "client": {"quantity": 30, "app_model": "udp_flood",
                   "app_options": {"interval": "100 ms", "size": 1470,
                                   "start_time": "100 ms",
                                   "start_spread": "100 ms"}},
    },
}


def flood(seed: int, uniform: bool = False) -> dict:
    """The flood's configuration; `uniform`: every host's ring at the deep
    depth instead."""
    cfg = copy.deepcopy(BASE)
    cfg["general"]["seed"] = seed
    if uniform:
        del cfg["hosts"]["server"]["router_queue_slots"]
        cfg["experimental"]["router_queue_slots"] = DEEP
    return cfg


def reference():
    from benchmark.spec import load_module

    return load_module(ROOT / "benchmark" / "reference" / "udp_flood.py")


def run(cfg: dict) -> dict:
    sim = build_simulation(cfg)
    pool = jax.device_get(sim.state.pool)
    live = pool.time != np.iinfo(np.int64).max
    starts = dict(zip(pool.dst[live].tolist(), pool.time[live].tolist()))
    sim.run(until=FRONTIER_NS)
    state = jax.device_get(sim.state)
    frontier = int(state.pool.time.min())
    return {"state": state, "chain": sim.audit_chain(),
            "frontier": frontier, "starts": starts}


@pytest.fixture(scope="module")
def deep_runs():
    return {seed: run(flood(seed)) for seed in SEEDS}


@pytest.mark.parametrize("seed", SEEDS)
def test_flood_matches_reference(deep_runs, seed):
    """Every host's counters and queue, its pending events, and the sent,
    delivered and CoDel-dropped totals are exactly the reference's."""
    from benchmark import compare

    ref = reference()
    got = deep_runs[seed]
    want = ref.flood(flood(seed), got["frontier"])
    checks = compare.compare(compare.observe(got["state"], ref), want, ref)
    assert compare.passed(checks), checks
    # through drop mode, with every datagram kept in the deep ring
    assert want["codel_dropped"] > 20 and max(want["drop_count"]) > 1
    assert want["overflow"] == 0
    router = got["state"].subs["router"]
    assert int(router.overflow_dropped) == 0
    assert int(router.deep_high_water) == int(want["peak"].max()) > 4


def test_deep_ring_is_a_uniform_ring(deep_runs):
    """Deep hosts behave exactly as in a uniform ring of the deep depth:
    the same audit chain and the same state, but for the rings' shapes."""
    seed = SEEDS[0]
    deep, uniform = deep_runs[seed], run(flood(seed, uniform=True))
    assert deep["chain"] == uniform["chain"]
    assert deep["frontier"] == uniform["frontier"]
    a, b = deep["state"], uniform["state"]
    ring = {"q_payload", "q_src", "q_enq_ts", "deep_rows", "deep_host",
            "deep_of", "deep_high_water"}
    for name, x, y in (("pool", a.pool, b.pool),
                       ("counters", a.counters, b.counters),
                       ("host", a.host, b.host)):
        for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            assert np.array_equal(u, v), name
    for sub in a.subs:
        if sub == "router":
            fields = set(a.subs[sub].__dataclass_fields__) - ring
            pairs = [(getattr(a.subs[sub], f), getattr(b.subs[sub], f))
                     for f in sorted(fields)]
        else:
            pairs = zip(jax.tree.leaves(a.subs[sub]),
                        jax.tree.leaves(b.subs[sub]))
        for u, v in pairs:
            assert np.array_equal(u, v), sub


def test_start_spread_is_a_seeded_stratified_permutation(deep_runs):
    """The clients take every slot of the grid once; each target's clients
    take one slot in each block of as many slots as there are targets;
    the seed decides the order."""
    clients = list(range(3, 33))
    target = np.arange(36) % 3
    grid = 30 * 1_000_000

    def offsets(seed, spread=grid):
        return _start_offsets(clients, target, 36, spread, seed)

    a = offsets(3)
    slot = a[clients] // 1_000_000
    assert sorted(slot) == list(range(30))
    for t in range(3):
        mine = slot[target[clients] == t]
        assert sorted(mine // 3) == list(range(10))
    assert not a[:3].any() and not a[33:].any()
    assert np.array_equal(a, offsets(3))
    assert not np.array_equal(a, offsets(4))
    assert not offsets(3, spread=0).any()
    # the build puts each client's first timer at start_time + its offset,
    # its clients targeting the servers (hosts 30, 31) round robin
    want = _start_offsets(list(range(30)), np.arange(32) % 2 + 30, 32,
                          100_000_000, SEEDS[0])
    starts = deep_runs[SEEDS[0]]["starts"]
    assert [starts[h] for h in range(30)] == list(100_000_000 + want[:30])


def _islands(cfg):
    cfg["experimental"]["num_shards"] = 2
    return build_simulation


def _fleet(cfg):
    from shadow_tpu.fleet import JobSpec, build_fleet

    return lambda c: build_fleet([JobSpec("a", c)])


def _mixed_depths(cfg):
    cfg["hosts"]["client"]["router_queue_slots"] = 64
    return build_simulation


def _phold(cfg):
    cfg["hosts"] = {"peer": {"quantity": 4, "app_model": "phold",
                             "router_queue_slots": 64}}
    return build_simulation


def _processes(cfg):
    from shadow_tpu.procs.builder import ProcessBuildError, \
        build_process_driver

    cfg["hosts"] = {"h": {"router_queue_slots": 64, "processes": [
        {"path": "/bin/true"}]}}

    def build(c):
        try:
            build_process_driver(c)
        except ProcessBuildError as e:
            raise BuildError(str(e)) from e

    return build


@pytest.mark.parametrize("layout", [_islands, _fleet, _mixed_depths,
                                    _phold, _processes])
def test_paths_without_a_deep_ring_refuse_it(layout):
    cfg = flood(1)
    build = layout(cfg)
    with pytest.raises(BuildError, match="router"):
        build(cfg)


def test_router_queue_slots_host_key():
    cfg = load_config(flood(1))
    depth = {h.name: h.router_queue_slots for h in cfg.hosts}
    assert depth["server1"] == DEEP and depth["client1"] is None
    bad = flood(1)
    bad["hosts"]["server"]["router_queue_slots"] = 0
    with pytest.raises(ConfigError, match="router_queue_slots"):
        load_config(bad)
