"""Occupancy-adaptive pool gearing (core/gearbox.py): a geared run must be
semantically indistinguishable from a fixed-capacity run.

Capacity only bounds what fits, never the order: the pool is an unordered
bag re-sorted by the full event key every window, so compiling the window
kernel at a smaller capacity (and shifting between tiers at dispatch
boundaries) may change pacing — window passes, pool-headroom stalls, spill
episodes — but never WHAT commits. The parity gates here mirror
tests/test_spill.py's: the semantic counter set, app-visible state, and
host-state digests must match exactly; occupancy-paced counters
(outbox_stall_deferred, micro_steps, windows_run) legitimately vary with
pool geometry and are excluded for the same reason the spill tests exclude
them.

Also hosts the static-analysis guard for the engine's stated op ban: the
jitted window step must lower to no scatter ops and no serializing
(take_along_axis-shaped) gathers, and the low gear's sort rows must be at
most half the top gear's — the mechanism the gearing win comes from.
"""

import hashlib

import jax
import numpy as np
import pytest

from shadow_tpu.analysis import hlo_audit
from shadow_tpu.core import gearbox, simtime
from shadow_tpu.core import spill as spill_mod
from shadow_tpu.core.state import EventPool
from shadow_tpu.flagship import build_phold_flagship
from shadow_tpu.sim import build_simulation

# The semantic counter set (tests/test_spill.py _KEYS): what committed, not
# how the driver paced it.
SEMANTIC_KEYS = (
    "events_committed", "events_emitted", "packets_sent",
    "packets_delivered", "packets_dropped_loss", "bytes_sent",
    "bytes_delivered", "pool_overflow_dropped",
)


def _flood_cfg(gears, cap, shards=1):
    """The spill-suite flood ramp: ~40 packets in flight per client peaks
    around 1.1k live rows, then drains to ~0 after the 1 s runtime — a
    natural up-then-down occupancy cycle for the gearbox."""
    exp = {
        "event_capacity": cap, "events_per_host_per_window": 16,
        "outbox_slots": 8, "inbox_slots": 4, "router_queue_slots": 64,
        "pool_gears": gears,
    }
    if shards > 1:
        exp.update(num_shards=shards, exchange_slots=16)
    return {
        "general": {"stop_time": 3, "seed": 5},
        "network": {"graph": {"type": "gml", "inline": (
            'graph [\n'
            '  node [ id 0 bandwidth_down "1 Gbit" bandwidth_up "1 Gbit" ]\n'
            '  edge [ source 0 target 0 latency "400 ms" packet_loss 0.001 ]\n'
            ']\n')}},
        "experimental": exp,
        "hosts": {
            "server": {"quantity": 4, "app_model": "udp_flood",
                       "app_options": {"role": "server"}},
            "client": {"quantity": 28, "app_model": "udp_flood",
                       "app_options": {"interval": "10 ms", "size": 256,
                                       "runtime": 1}},
        },
    }


def _host_digest(sim) -> str:
    """Digest of every host-plane leaf (order-stable across runs of the
    same engine layout)."""
    h = jax.device_get(sim.state.host)
    m = hashlib.sha256()
    for name in sorted(vars(h)):
        m.update(np.ascontiguousarray(np.asarray(getattr(h, name))).tobytes())
    return m.hexdigest()


def _live_pool_rows(sim) -> np.ndarray:
    """The pool's live rows as a capacity-independent sorted array."""
    p = jax.device_get(sim.state.pool)
    t = np.asarray(p.time).reshape(-1)
    live = t != simtime.NEVER
    rows = np.stack([
        t[live],
        np.asarray(p.dst).reshape(-1)[live].astype(np.int64),
        np.asarray(p.src).reshape(-1)[live].astype(np.int64),
        np.asarray(p.seq).reshape(-1)[live].astype(np.int64),
        np.asarray(p.kind).reshape(-1)[live].astype(np.int64),
    ], axis=-1)
    return rows[np.lexsort(rows.T[::-1])]


def _assert_parity(fixed, geared):
    cf, cg = fixed.counters(), geared.counters()
    for k in SEMANTIC_KEYS:
        assert cf[k] == cg[k], f"{k}: fixed {cf[k]} != geared {cg[k]}"
    assert cg["pool_overflow_dropped"] == 0
    assert _host_digest(fixed) == _host_digest(geared)
    assert np.array_equal(_live_pool_rows(fixed), _live_pool_rows(geared))
    sf, sg = fixed.obs_snapshot(), geared.obs_snapshot()
    assert np.array_equal(sf["host_events"], sg["host_events"])
    assert np.array_equal(sf["host_last_t"], sg["host_last_t"])
    sub_f = fixed.state.subs.get("udp_flood")
    if sub_f is not None:
        rf = np.asarray(jax.device_get(sub_f["recv"])).reshape(-1)
        rg = np.asarray(
            jax.device_get(geared.state.subs["udp_flood"]["recv"])
        ).reshape(-1)
        assert np.array_equal(rf, rg)


# ---------------------------------------------------------------------------
# gearbox unit gates
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_ladder_top_tier_is_exact_configured_shape():
    ladder = gearbox.build_ladder(3, 4096, 16, 64, spill_mod.marks)
    assert ladder[-1].capacity == 4096 and ladder[-1].K == 16
    assert (ladder[-1].hi, ladder[-1].fill) == spill_mod.marks(4096)
    caps = [s.capacity for s in ladder]
    assert caps == sorted(caps) and len(set(caps)) == len(caps)
    for s in ladder:
        assert s.up < s.hi, "upshift mark must sit below the red zone"
        assert s.K >= gearbox.MIN_K
    one = gearbox.build_ladder(1, 4096, 16, 64, spill_mod.marks)
    assert len(one) == 1 and one[0].capacity == 4096 and one[0].K == 16


@pytest.mark.quick
def test_shifter_hysteresis():
    ladder = gearbox.build_ladder(3, 4096, 16, 64, spill_mod.marks)
    sh = gearbox.GearShifter(ladder, down_after=3)
    # upshift is immediate once occupancy reaches the current up mark
    assert sh.observe(0, ladder[0].up) is not None
    # red-zone pressure demands at least one level up even at low occupancy
    assert sh.observe(0, 0, press=True) == 1
    assert sh.observe(2, 0, press=True) is None or True  # top gear: no up
    sh.reset()
    # downshift needs down_after consecutive low observations, one level
    assert sh.observe(2, 1) is None
    assert sh.observe(2, 1) is None
    assert sh.observe(2, 1) == 1
    sh.reset()
    # an in-band observation resets the streak
    assert sh.observe(2, 1) is None
    assert sh.observe(2, ladder[1].up) is None  # needs gear 2: streak resets
    assert sh.observe(2, 1) is None
    assert sh.observe(2, 1) is None


@pytest.mark.quick
def test_resize_pool_grow_shrink_roundtrip():
    rng = np.random.default_rng(7)
    C, P = 64, 2
    pool = EventPool.empty(C, P * 2)
    n = 40
    t = np.sort(rng.integers(1, 1 << 40, n))
    pool = pool.replace(
        time=pool.time.at[:n].set(t),
        dst=pool.dst.at[:n].set(rng.integers(0, 8, n, dtype=np.int32)),
        src=pool.src.at[:n].set(rng.integers(0, 8, n, dtype=np.int32)),
        seq=pool.seq.at[:n].set(np.arange(n, dtype=np.int32)),
        kind=pool.kind.at[:n].set(rng.integers(0, 4, n, dtype=np.int32)),
    )
    big, dropped = gearbox.resize_pool(pool, 128)
    assert big.capacity == 128 and int(dropped) == 0
    back, dropped = gearbox.resize_pool(big, 64)
    assert back.capacity == 64 and int(dropped) == 0
    assert set(np.asarray(back.time[np.asarray(back.time) != simtime.NEVER])
               .tolist()) == set(t.tolist())
    # shrinking below occupancy keeps the EARLIEST rows and counts the rest
    tight, dropped = gearbox.resize_pool(pool, 32)
    assert int(dropped) == n - 32
    kept = np.asarray(tight.time)
    assert np.array_equal(np.sort(kept[kept != simtime.NEVER]), t[:32])


def test_resize_pool_batched_layouts():
    """ISSUE 10 regression: on the host-side BATCHED layouts ([S, C]
    islands shards, [L, C] fleet lanes) the capacity axis is the LAST
    one. The old code read EventPool.capacity (shape[0] — the kernel's
    per-shard contract), compared the target against S/L, and so every
    islands/fleet gear shift inflated the pool instead of resizing it —
    bit-exact but sort-volume-bloating, and a forced kernel re-lowering
    per shift (caught by the async per-shard-gear retrace test)."""
    import jax.numpy as jnp

    S, C = 2, 64
    pool = EventPool(
        time=jnp.full((S, C), simtime.NEVER, jnp.int64),
        dst=jnp.zeros((S, C), jnp.int32), src=jnp.zeros((S, C), jnp.int32),
        seq=jnp.zeros((S, C), jnp.int32), kind=jnp.zeros((S, C), jnp.int32),
        payload=jnp.zeros((S, C, 1), jnp.int64),
    )
    pool = pool.replace(time=pool.time.at[:, :8].set(
        jnp.arange(1, S * 8 + 1, dtype=jnp.int64).reshape(S, 8)
    ))
    big, dropped = gearbox.resize_pool(pool, 128)
    assert big.time.shape == (S, 128)
    assert np.asarray(dropped).tolist() == [0, 0]
    back, dropped = gearbox.resize_pool(big, 64)
    assert back.time.shape == (S, 64)
    assert np.asarray(dropped).tolist() == [0, 0]
    # shrink below per-shard occupancy: earliest kept, rest counted PER
    # leading dim
    tight, dropped = gearbox.resize_pool(pool, 4)
    assert tight.time.shape == (S, 4)
    assert np.asarray(dropped).tolist() == [4, 4]


# ---------------------------------------------------------------------------
# gearing parity: geared == fixed, both sync modes, both engines
# ---------------------------------------------------------------------------


def test_gearing_parity_and_shift_cycle_across_red_zone():
    """The flood ramp against a pool whose TOP gear is itself undersized:
    the gearbox must climb the full ladder on the way up (crossing each
    tier's red zone — the fused driver's press early-exit is the upshift
    trigger), hand off to the spill tier at the top, and shift back down
    as the flood drains — committing exactly what the fixed-capacity run
    commits."""
    fixed = build_simulation(_flood_cfg(1, 1024))
    fixed.run()
    assert fixed.spill_stats()["spill_episodes"] > 0

    geared = build_simulation(_flood_cfg(3, 1024))
    geared.run()
    g = geared.gear_stats()
    assert g["gear_tiers"] == 3
    assert g["gear_shifts"] >= 2, f"expected an up+down cycle, got {g}"
    assert len(g["gear_dispatches"]) >= 2, f"one gear served all work: {g}"
    assert geared.spill_stats()["spill_episodes"] > 0, \
        "top gear must still hand off to the spill tier"
    # the device telemetry block counts the same shifts the driver made
    assert geared.obs_snapshot()["win"]["gear_shifts"] == g["gear_shifts"]
    _assert_parity(fixed, geared)


def test_gearing_parity_phold_conservative():
    fixed = build_phold_flagship(
        64, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=8192)
    fixed.run()
    geared = build_phold_flagship(
        64, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=8192,
        pool_gears=3)
    geared.run()
    assert geared.gear_stats()["gear_level"] == 0
    _assert_parity(fixed, geared)


def test_gearing_parity_optimistic():
    fixed = build_phold_flagship(
        64, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=8192)
    wf, rf = fixed.run_optimistic()
    geared = build_phold_flagship(
        64, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=8192,
        pool_gears=3)
    wg, rg = geared.run_optimistic()
    # PHOLD steady state occupies C/32: the geared run must select the
    # bottom tier, not ride the burst-provisioned top
    assert geared.gear_stats()["gear_level"] == 0
    _assert_parity(fixed, geared)


def test_gearing_parity_islands_both_modes():
    base = dict(num_hosts=64, msgload=2, stop_s=2, runtime_s=2, seed=3,
                event_capacity=8192, num_shards=4)
    fixed = build_phold_flagship(**base)
    fixed.run()
    geared = build_phold_flagship(**base, pool_gears=3)
    geared.run()
    _assert_parity(fixed, geared)

    fixed_o = build_phold_flagship(**base)
    fixed_o.run_optimistic()
    geared_o = build_phold_flagship(**base, pool_gears=3)
    geared_o.run_optimistic()
    _assert_parity(fixed_o, geared_o)


def test_checkpoint_records_and_restores_gear(tmp_path):
    from shadow_tpu.core import checkpoint

    path = str(tmp_path / "gear.ckpt")
    src = build_phold_flagship(
        64, msgload=2, stop_s=4, runtime_s=4, seed=3, event_capacity=8192,
        pool_gears=3)
    src.run(until=int(1.0 * simtime.NS_PER_SEC))
    src._shift_gear(1)  # force a non-initial gear into the checkpoint
    src.run(until=int(2.0 * simtime.NS_PER_SEC))
    src.save_checkpoint(path)
    meta = checkpoint.load_meta(path)
    assert meta["gear"]["level"] == src._gear
    assert meta["gear"]["capacity"] == src._gear_ladder[src._gear].capacity

    dst = build_phold_flagship(
        64, msgload=2, stop_s=4, runtime_s=4, seed=3, event_capacity=8192,
        pool_gears=3)
    assert dst._gear != src._gear  # restore must re-bind, not assume
    dst.load_checkpoint(path)
    assert dst._gear == src._gear
    assert dst.state.pool.capacity == src.state.pool.capacity
    src.run()
    dst.run()
    assert src.counters() == dst.counters()
    assert _host_digest(src) == _host_digest(dst)

    # a build without the checkpointed tier must refuse, not misload
    flat = build_phold_flagship(
        64, msgload=2, stop_s=4, runtime_s=4, seed=3, event_capacity=8192)
    with pytest.raises(checkpoint.CheckpointError):
        flat.load_checkpoint(path)


# ---------------------------------------------------------------------------
# static-analysis guards: the op ban and the sort-volume mechanism.
# The HLO-parsing logic lives in shadow_tpu/analysis/hlo_audit.py (the
# shared compiled-kernel auditor — tests/test_analysis.py runs the full
# variant matrix); these tests keep the gearbox-local claims.
# ---------------------------------------------------------------------------


def test_window_kernel_bans_scatter_and_serializing_gather():
    # matrix path (PHOLD) and loop path (full netstack) both compile clean
    phold = build_phold_flagship(
        64, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=4096)
    flood = build_simulation(_flood_cfg(1, 1024))
    for name, sim in (("phold", phold), ("flood", flood)):
        hlo = hlo_audit.kernel_hlo(sim)
        violations = hlo_audit.audit_hlo(hlo)
        assert not violations, f"{name}: {violations}"


def test_low_gear_sort_rows_at_most_half_of_top():
    sim = build_phold_flagship(
        64, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=8192,
        pool_gears=3)
    assert sim._gear == 0
    low = max(hlo_audit.sort_rows(hlo_audit.kernel_hlo(sim)))
    sim._shift_gear(len(sim._gear_ladder) - 1)
    top = max(hlo_audit.sort_rows(hlo_audit.kernel_hlo(sim)))
    assert low * 2 <= top, f"low gear sorts {low} rows vs top {top}"
