"""Benchmark: on-device PHOLD throughput vs a CPU sequential-DES baseline.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The workload is the PHOLD PDES canary (reference src/test/phold/phold.yaml:
peers over a 50ms self-loop link exchanging random-destination messages),
scaled up. `value` is committed events/sec on the device for the full fused
run (one XLA while_loop program). `vs_baseline` is the speedup over the
reference-replica C++ scheduler (native/baseline/phold_baseline.cpp): the
reference itself cannot build in this image (its config/worker layer needs
cargo/rustc, plus glib and igraph — none present, zero egress), so the
replica reimplements its exact hot path — per-host locked priority queues,
worker threads, conservative windows, (time,dst,src,seq) total order — in
C++ at -O2 and runs the same PHOLD workload on this machine's CPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def _enable_compile_cache():
    """Persistent XLA compile cache: the staged configs compile multi-minute
    programs; cache them so reruns start in seconds. The root is SHARED
    with the serve daemon's AOT kernel cache (shadow_tpu/serve/kcache.py
    enable_xla_cache: $JAX_COMPILATION_CACHE_DIR when set, else
    $SHADOW_TPU_CACHE_DIR or .jax_cache next to the repo), so daemon and
    bench warm each other."""
    from shadow_tpu.serve.kcache import enable_xla_cache

    cache, evicted = enable_xla_cache()
    if evicted:
        print(f"# compile cache: evicted {evicted} corrupt entr"
              f"{'y' if evicted == 1 else 'ies'} from {cache}",
              file=sys.stderr)


def _cpu_child_env(cache_dir: str) -> dict:
    """Environment of a serve/route child of a CPU gate: pinned to the CPU
    (the parent may hold the chip, and a chip belongs to one process) and
    to the gate's own fresh AOT cache root, so its cold -> warm is real."""
    return {**os.environ, "JAX_PLATFORMS": "cpu",
            "SHADOW_TPU_CACHE_DIR": cache_dir}


def _require_tpu():
    """The measurement path runs on the chip or not at all: a CPU number
    is never reported under a device metric."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the TPU; JAX found {dev.platform!r} "
            f"({dev.device_kind}). The CPU gates are the --*-smoke flags."
        )
    return dev


def device_phold(num_hosts: int, msgload: int, stop_s: int,
                 windows_per_dispatch: int = 64, num_shards: int = 1):
    import jax

    from shadow_tpu.core import simtime
    from shadow_tpu.flagship import build_phold_flagship

    sim = build_phold_flagship(
        num_hosts, msgload=msgload, stop_s=stop_s, runtime_s=stop_s,
        num_shards=num_shards,
    )
    # Warm-up compile (cached), then timed run.
    sim.run(until=int(0.2 * simtime.NS_PER_SEC),
            windows_per_dispatch=windows_per_dispatch)
    jax.block_until_ready(sim.state.pool.time)
    t0 = time.perf_counter()
    sim.run(windows_per_dispatch=windows_per_dispatch)
    jax.block_until_ready(sim.state.pool.time)
    wall = time.perf_counter() - t0
    c = sim.counters()
    return c["events_committed"], wall, stop_s / wall


_REPO = os.path.dirname(os.path.abspath(__file__))
_BASELINE_SRC = os.path.join(_REPO, "native", "baseline", "phold_baseline.cpp")
_BASELINE_BIN = os.path.join(_REPO, "native", "build", "phold_baseline")


def cpp_phold_baseline(num_hosts: int, msgload: int, stop_s: int,
                       workers: int = 0):
    """Run the reference-replica C++ scheduler (see module docstring) on the
    same PHOLD parameters; returns its parsed JSON result. workers=0 means
    one per online CPU (the reference's recommended parallelism,
    configuration.rs:141-147)."""
    if not os.path.exists(_BASELINE_BIN) or (
        os.path.getmtime(_BASELINE_BIN) < os.path.getmtime(_BASELINE_SRC)
    ):
        os.makedirs(os.path.dirname(_BASELINE_BIN), exist_ok=True)
        subprocess.run(
            ["g++", "-O2", "-pthread", "-o", _BASELINE_BIN, _BASELINE_SRC],
            check=True,
        )
    # runtime == stop: hosts forward for the whole run, matching
    # device_phold's build (runtime_s=stop_s).
    out = subprocess.run(
        [_BASELINE_BIN, str(num_hosts), str(msgload), "50", str(stop_s),
         str(stop_s), str(workers), "42"],
        check=True, capture_output=True, text=True,
    )
    return json.loads(out.stdout)


def _run_stage(stage: str, app_model: str, loss: float, app_options: dict,
               extra_counters: tuple = (), num_hosts: int = 10240,
               stop_s: int = 4, event_capacity: int = 1 << 15,
               extra_experimental: dict | None = None,
               windows_per_dispatch: int = 8, num_shards: int = 1,
               sync: str = "conservative"):
    """Build, warm up (compile + bootstrap), then time the remaining sim
    span. Warm-up-committed events are subtracted so the reported rate and
    sim/wall ratio cover only the timed segment."""
    import jax

    from shadow_tpu.flagship import stack_config
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.sim import build_simulation

    warmup_ns = 1_500_000_000
    cfg = stack_config(
        app_model, num_hosts, loss=loss, app_options=app_options,
        stop_s=stop_s, event_capacity=event_capacity,
        experimental={
            **({"num_shards": num_shards} if num_shards > 1 else {}),
            **(extra_experimental or {}),
        },
    )
    sim = build_simulation(cfg)
    # Telemetry session: wall histograms + per-round throughput ride the
    # handoff boundaries the driver already syncs at; the device counter
    # block is compiled into the kernel either way.
    session = obs_metrics.ObsSession()
    sim.obs_session = session
    # windows_per_dispatch bounds each dispatch; every dispatch boundary
    # is a host sync, so it is sized as large as the stage allows.
    windows = rollbacks = None
    if sync == "optimistic":
        # BASELINE config 4's sync mode: adaptive speculative windows
        # (engine.run_optimistic); warm-up compiles the attempt kernel
        sim.run_optimistic(until=warmup_ns)
        jax.block_until_ready(sim.state.pool.time)
        warm_events = sim.counters()["events_committed"]
        t0 = time.perf_counter()
        # timed-segment counts only, consistent with events_per_sec
        windows, rollbacks = sim.run_optimistic()
        jax.block_until_ready(sim.state.pool.time)
        wall = time.perf_counter() - t0
    else:
        sim.run(until=warmup_ns, windows_per_dispatch=windows_per_dispatch)
        jax.block_until_ready(sim.state.pool.time)
        warm_events = sim.counters()["events_committed"]
        t0 = time.perf_counter()
        sim.run(windows_per_dispatch=windows_per_dispatch)
        jax.block_until_ready(sim.state.pool.time)
        wall = time.perf_counter() - t0
    c = sim.counters()
    timed_events = c["events_committed"] - warm_events
    timed_sim_s = stop_s - warmup_ns / 1e9
    spill_st = sim.spill_stats()
    out = {
        "stage": stage,
        "hosts": num_hosts,
        "num_shards": num_shards,
        "sync": sync,
        "events_per_sec": round(timed_events / wall, 1),
        "packets_delivered": c["packets_delivered"],
        "sim_sec_per_wall_sec": round(timed_sim_s / wall, 2),
        # must stay 0 or the measurement dropped work
        "pool_overflow_dropped": c["pool_overflow_dropped"],
    }
    if windows is not None:
        out["windows"] = windows
        out["rollbacks"] = rollbacks
    if spill_st.get("spill_episodes"):
        out.update(spill_st)  # the never-drop tier fired: record its cost
    for k in extra_counters:
        out[k] = c[k]
    # compact telemetry sub-object: the signals every perf comparison
    # needs, pulled from the device block + wall histograms (the full
    # document is what --metrics-out dumps)
    session.finalize(sim)
    doc = session.metrics.to_doc()
    hist = doc["histograms"]
    gear = sim.gear_stats()
    out["metrics"] = {
        # which backend ran the row
        "platform": jax.default_backend(),
        "windows_run": doc["counters"].get("obs.windows_run", 0),
        "matrix_dispatches": doc["counters"].get("obs.matrix_dispatches", 0),
        "loop_dispatches": doc["counters"].get("obs.loop_dispatches", 0),
        "window_shrinks": doc["counters"].get("obs.window_shrinks", 0),
        "vtime_spread_ns": doc["gauges"].get("vtime.spread_ns", 0),
        "dispatch_p50_s": round(
            hist.get("wall.dispatch_s", {}).get("p50", 0.0), 4
        ),
        "round_events_per_sec_p50": round(
            hist.get("round.events_per_sec", {}).get("p50", 0.0), 1
        ),
        # gearbox telemetry (core/gearbox.py): active level, shift count,
        # and the per-gear dispatch histogram
        "gear_level": gear["gear_level"],
        "gear_tiers": gear["gear_tiers"],
        "gear_shifts": gear["gear_shifts"],
        "gear_dispatches": gear["gear_dispatches"],
    }
    return out


def stage_udp_flood(num_hosts: int = 10240, stop_s: int = 4):
    """BASELINE staged config 2: 10k-host UDP flood through the full device
    network stack (NIC token buckets, CoDel router, UDP sockets)."""
    # Shapes tuned from the on-chip profile (tools/profile_flood.py): the
    # extraction/merge sorts carry C + H*(K+1) rows (+ H*(O+B) box rows in
    # the merge) and are ~60% of device time — K/O/C are sized to the
    # workload's Poisson tails, no further.
    return _run_stage(
        "udp_flood_10k", "udp_flood", 0.001,
        {"interval": "20 ms", "size": 1024, "runtime": stop_s - 1},
        # 1 << 14 pool capacity measurably overflows (1.5k drops); 1 << 15
        # does not
        num_hosts=num_hosts, stop_s=stop_s, event_capacity=1 << 15,
        extra_experimental={"events_per_host_per_window": 12,
                            "outbox_slots": 8},
        windows_per_dispatch=32,
    )


def stage_tcp_bulk(num_hosts: int = 10240, stop_s: int = 4):
    """BASELINE staged config 3: 10k-host TCP bulk transfer (vmap'd
    handshake + seq/ack + Reno congestion state machines)."""
    return _run_stage(
        "tcp_bulk_10k", "tcp_bulk", 0.0005, {"total": "64 KiB"},
        extra_counters=("bytes_delivered",),
        # in-flight population ~25 events/client (cwnd segments + ACKs +
        # pump/timer events): 1 << 16 measurably overflows, 1 << 18 does not
        num_hosts=num_hosts, stop_s=stop_s, event_capacity=1 << 18,
        # TCP self-events (timers + pumps) need more inbox headroom than
        # the UDP stage; the TCP handler suite's worst-case emission count
        # per event is 28 (engine probe), so the outbox must cover it —
        # O=16 fails the build-time probe (this is what blocked the r2
        # stage-3 recording)
        extra_experimental={"inbox_slots": 8, "outbox_slots": 32},
    )


def stage_phold_100k(stop_s: int = 10):
    """BASELINE staged configs 4-5 shape probe: 100k hosts on ONE chip
    (matrix fast path). msgload 2 → 20M+ committed events, in 4-window
    dispatches."""
    num_hosts, msgload = 100_000, 2
    events, wall, sim_per_wall = device_phold(
        num_hosts, msgload, stop_s, windows_per_dispatch=4
    )
    base = cpp_phold_baseline(num_hosts, msgload, stop_s)
    rate = events / wall if wall > 0 else 0.0
    return {
        "stage": "phold_100k",
        "hosts": num_hosts,
        "events_per_sec": round(rate, 1),
        "sim_sec_per_wall_sec": round(sim_per_wall, 2),
        "vs_baseline": round(rate / (base["events_per_sec"] or 1.0), 3),
    }


def stage_udp_flood_50k(sync: str = "conservative", stop_s: int = 3,
                        num_shards: int = 1):
    """BASELINE staged config 4 shape: 50k hosts through the full device
    network stack, in BOTH sync modes (config 4 pairs this scale with
    optimistic PDES windows; conservative is the control row) — and, with
    num_shards > 1, on the ISLANDS runner in both modes (virtual islands
    batch the local sorts S× smaller; optimistic×islands is the round-5
    engine work)."""
    return _run_stage(
        "udp_flood_50k", "udp_flood", 0.001,
        {"interval": "40 ms", "size": 1024, "runtime": stop_s - 1},
        num_hosts=50176,  # 49 * 1024
        stop_s=stop_s, event_capacity=1 << 17,
        extra_experimental={"events_per_host_per_window": 12,
                            "outbox_slots": 8},
        windows_per_dispatch=16, sync=sync, num_shards=num_shards,
    )


def stage_spill_50k(stop_s: int = 3):
    """Deliberately undersized pool at the 50k shape (VERDICT r4 #6): the
    spill tier must complete the run — measure what the never-drop
    guarantee costs at scale (episodes, drained/injected rows, sim/wall vs
    the right-sized conservative row)."""
    return _run_stage(
        "udp_flood_50k_spill", "udp_flood", 0.001,
        {"interval": "40 ms", "size": 1024, "runtime": stop_s - 1},
        num_hosts=50176, stop_s=stop_s,
        # a quarter of the right-sized pool: guaranteed spill episodes
        event_capacity=1 << 15,
        extra_experimental={"events_per_host_per_window": 12,
                            "outbox_slots": 8},
        windows_per_dispatch=16,
    )


def stage_udp_flood_100k(stop_s: int = 3):
    """100k hosts through the full device network stack on one chip."""
    return _run_stage(
        "udp_flood_100k", "udp_flood", 0.001,
        {"interval": "40 ms", "size": 1024, "runtime": stop_s - 1},
        num_hosts=100_352,  # 98 * 1024: divisible for future mesh splits
        stop_s=stop_s, event_capacity=1 << 18,
    )


def stage_obs_overhead(num_hosts: int = 8192, msgload: int = 4,
                       stop_s: int = 4):
    """Telemetry-plane overhead smoke row (ISSUE 1 acceptance gate): the
    flagship PHOLD shape with the device counter block compiled IN vs OUT
    (experimental.obs_counters). The block costs one fused [NUM_WIN] add
    plus two [H] selects per window step; the gate is <= 3% step time."""
    import jax

    from shadow_tpu.core import simtime
    from shadow_tpu.flagship import build_phold_flagship

    def timed(obs_on: bool) -> tuple[float, int]:
        sim = build_phold_flagship(
            num_hosts, msgload=msgload, stop_s=stop_s, runtime_s=stop_s,
            obs_counters=obs_on,
        )
        sim.run(until=int(0.2 * simtime.NS_PER_SEC))
        jax.block_until_ready(sim.state.pool.time)
        t0 = time.perf_counter()
        sim.run()
        jax.block_until_ready(sim.state.pool.time)
        return time.perf_counter() - t0, sim.counters()["events_committed"]

    # interleave the arms to decorrelate machine drift from the comparison
    w_on = min(timed(True)[0] for _ in range(2))
    w_off, events = timed(False)
    w_off = min(w_off, timed(False)[0])
    overhead = (w_on - w_off) / w_off * 100.0 if w_off > 0 else 0.0
    return {
        "stage": "obs_overhead",
        "hosts": num_hosts,
        "events": int(events),
        "wall_obs_on_s": round(w_on, 3),
        "wall_obs_off_s": round(w_off, 3),
        "overhead_pct": round(overhead, 2),
        "gate_3pct": overhead <= 3.0,
    }


def stage_audit_smoke(num_hosts: int = 8192, msgload: int = 4,
                      stop_s: int = 4, flight_capacity: int = 64):
    """Determinism-audit gate (ISSUE 5 acceptance): the flagship PHOLD
    shape with the digest chain + flight ring compiled IN vs OUT — the
    folds are fused i64 arithmetic and one-hot ring writes per window
    step, gated at ≤ 3% step time. Also asserts the chain is identical
    across two seeded reruns, and that the divergence bisector pinpoints
    the exact forged window (the diff engine behind tools/diff_digest.py).
    Writes a schema-v5 metrics artifact (audit.* namespace) that
    tools/validate_metrics.py can schema-gate."""
    import copy
    import tempfile

    import jax
    import numpy as np

    from shadow_tpu.core import simtime
    from shadow_tpu.flagship import build_phold_flagship
    from shadow_tpu.obs import audit as audit_mod
    from shadow_tpu.obs import metrics as obs_metrics

    def timed(audit_on: bool, flight: int, seed: int = 42):
        sim = build_phold_flagship(
            num_hosts, msgload=msgload, stop_s=stop_s, runtime_s=stop_s,
            seed=seed, audit_digest=audit_on, flight_recorder=flight,
        )
        sim.run(until=int(0.2 * simtime.NS_PER_SEC))
        jax.block_until_ready(sim.state.pool.time)
        t0 = time.perf_counter()
        sim.run()
        jax.block_until_ready(sim.state.pool.time)
        return time.perf_counter() - t0, sim

    # interleave the arms to decorrelate machine drift from the comparison
    w_aud, sim1 = timed(True, flight_capacity)
    w_base, _ = timed(False, 0)
    w2, sim2 = timed(True, flight_capacity)
    w_aud = min(w_aud, w2)
    w_base = min(w_base, timed(False, 0)[0])
    overhead = (w_aud - w_base) / w_base * 100.0 if w_base > 0 else 0.0
    chain1, chain2 = sim1.audit_chain(), sim2.audit_chain()

    # divergence bisection: two seeded reruns dump identical digest docs;
    # forging one mid-run record (and one host sub-chain) must be
    # pinpointed to the exact window and host
    tiny = dict(num_hosts=1024, msgload=2, stop_s=2, runtime_s=2)
    with tempfile.TemporaryDirectory(prefix="audit_smoke_") as td:
        docs = []
        for i in range(2):
            s = build_phold_flagship(audit_digest=True, **tiny)
            s.attach_audit(meta={"arm": i})
            s.run(windows_per_dispatch=4)
            docs.append(s.write_digest(os.path.join(td, f"d{i}.json")))
    clean = audit_mod.diff_digest_docs(docs[0], docs[1])
    forged = copy.deepcopy(docs[1])
    k = len(forged["records"]) // 2
    forged["records"][k]["chain"] ^= 0x5A5A
    forged["hosts"][3] = (forged["hosts"][3] ^ 0x5A5A) & ((1 << 64) - 1)
    forged["final"]["chain"] = audit_mod.combine(
        np.asarray(forged["hosts"], dtype=np.uint64)
    )
    rep = audit_mod.diff_digest_docs(docs[0], forged)
    first = rep["first_divergent_record"] or {}
    forged_found = (
        first.get("seq_a") == docs[0]["records"][k]["seq"]
        and rep["divergent_hosts"] == [3]
    )

    # schema-v5 metrics artifact with the audit.* namespace, referenced
    # from this row so a reader can schema-gate it
    metrics_path = os.path.join(_REPO, "audit_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(sim1)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "audit_smoke", "hosts": num_hosts,
    })
    obs_metrics.validate_metrics_doc(doc)

    gate_3 = overhead <= 3.0
    return {
        "stage": "audit_smoke",
        "hosts": num_hosts,
        "flight_capacity": flight_capacity,
        "wall_base_s": round(w_base, 3),
        "wall_audit_s": round(w_aud, 3),
        "overhead_pct": round(overhead, 2),
        "gate_3pct": gate_3,
        "chain": int(chain1),
        "chains_equal": chain1 == chain2 and chain1 != 0,
        "rerun_docs_identical": clean["identical"],
        "forged_window_found": forged_found,
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate": bool(
            gate_3 and chain1 == chain2 and chain1 != 0
            and clean["identical"] and forged_found
        ),
    }


def stage_gear_win(num_hosts: int = 8192, msgload: int = 4, stop_s: int = 4):
    """Gearing win smoke row (ISSUE 2 acceptance gate): the flagship PHOLD
    shape with the pool oversized 8× above steady-state occupancy — the
    burst-provisioned pool every production config carries — run fixed
    (pool_gears=1) vs geared (pool_gears=3, engages the C/4 tier). Gate:
    geared per-window wall time ≥ 25% better at occupancy ≤ C/4."""
    import jax

    from shadow_tpu.core import simtime
    from shadow_tpu.flagship import build_phold_flagship

    # live population = num_hosts * msgload; capacity 8x above it
    capacity = 8 * num_hosts * msgload

    def timed(gears: int):
        sim = build_phold_flagship(
            num_hosts, msgload=msgload, stop_s=stop_s, runtime_s=stop_s,
            event_capacity=capacity, pool_gears=gears,
        )
        sim.run(until=int(0.2 * simtime.NS_PER_SEC))
        jax.block_until_ready(sim.state.pool.time)
        t0 = time.perf_counter()
        sim.run()
        jax.block_until_ready(sim.state.pool.time)
        wall = time.perf_counter() - t0
        snap = sim.obs_snapshot()
        windows = snap["win"]["windows_run"] if snap else 0
        return wall, windows, sim.counters()["events_committed"], \
            sim.gear_stats()

    # interleave the arms to decorrelate machine drift from the comparison
    w_fix, n_fix, ev_fix, _ = timed(1)
    w_gear, n_gear, ev_gear, gear = timed(3)
    w_fix = min(w_fix, timed(1)[0])
    w_gear = min(w_gear, timed(3)[0])
    per_win_fix = w_fix / max(n_fix, 1)
    per_win_gear = w_gear / max(n_gear, 1)
    win_pct = (1.0 - per_win_gear / per_win_fix) * 100.0 if per_win_fix else 0.0
    return {
        "stage": "gear_win",
        "hosts": num_hosts,
        "pool_capacity": capacity,
        "occupancy": num_hosts * msgload,
        "events_fixed": int(ev_fix),
        "events_geared": int(ev_gear),
        "events_equal": ev_fix == ev_gear,
        "windows_fixed": int(n_fix),
        "windows_geared": int(n_gear),
        "wall_fixed_s": round(w_fix, 3),
        "wall_geared_s": round(w_gear, 3),
        "per_window_fixed_ms": round(per_win_fix * 1e3, 4),
        "per_window_geared_ms": round(per_win_gear * 1e3, 4),
        "win_pct": round(win_pct, 2),
        "gate_25pct": win_pct >= 25.0,
        "gear": gear,
    }


def stage_fault_smoke():
    """Fault-plane smoke row (ISSUE 3 acceptance gate): a quarantine-mode
    managed-process run with ONE injected kill_proc mid-run must complete
    with rc=0 (the unaffected pair finishes; the faulted process is
    excluded from plugin-error accounting) and record faults.* metrics
    (hosts_quarantined, injections_fired)."""
    import contextlib
    import io
    import pathlib
    import shutil
    import tempfile

    from shadow_tpu.procs import build as build_mod

    if not build_mod.toolchain_available():
        return {"stage": "fault_smoke", "error": "no native toolchain",
                "gate_rc0": False, "gate_metrics": False}

    tmp = tempfile.mkdtemp(prefix="shadow_tpu_fault_smoke_")
    try:
        cc = shutil.which("cc") or shutil.which("gcc")
        apps = {}
        for stem in ("udp_echo_server", "udp_echo_client"):
            src = pathlib.Path(_REPO) / "tests" / "apps" / f"{stem}.c"
            exe = pathlib.Path(tmp) / stem
            subprocess.run(
                [cc, "-O1", "-o", str(exe), str(src), "-lpthread"],
                check=True, capture_output=True,
            )
            apps[stem] = str(exe)

        from shadow_tpu.__main__ import _run_process_plane
        from shadow_tpu.core.config import load_config
        from shadow_tpu.procs.builder import build_process_driver

        gml = (
            'graph [\n'
            '  node [ id 0 bandwidth_down "100 Mbit" '
            'bandwidth_up "100 Mbit" ]\n'
            '  edge [ source 0 target 0 latency "50 ms" '
            'packet_loss 0.0 ]\n'
            ']\n'
        )
        # pair A completes normally; pair B's client (40 pings x 100 ms
        # RTT: busy until ~5 s) is killed at 3 s and its host quarantined
        cfg = load_config({
            "general": {"stop_time": "6 s", "seed": 7},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "faults": {
                "on_proc_failure": "quarantine",
                "inject": [
                    {"at": "3 s", "op": "kill_proc", "proc": "clientb.0"},
                ],
            },
            "hosts": {
                "servera": {"processes": [
                    {"path": apps["udp_echo_server"], "args": "9000 3"}]},
                "clienta": {"processes": [
                    {"path": apps["udp_echo_client"],
                     "args": "servera 9000 3", "start_time": "1 s"}]},
                "serverb": {"processes": [
                    {"path": apps["udp_echo_server"], "args": "9000 40"}]},
                "clientb": {"processes": [
                    {"path": apps["udp_echo_client"],
                     "args": "serverb 9000 40", "start_time": "1 s"}]},
            },
        })
        driver = build_process_driver(
            cfg, data_root=pathlib.Path(tmp) / "data"
        )
        out = io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(out):
            rc = _run_process_plane(cfg, driver, False)
        stats = {k: int(v) for k, v in sorted(driver.fault_stats().items())}
        return {
            "stage": "fault_smoke",
            "rc": rc,
            "faults": stats,
            "gate_rc0": rc == 0,
            "gate_metrics": (
                stats.get("hosts_quarantined", 0) >= 1
                and stats.get("injections_fired", 0) >= 1
            ),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _fleet_smoke_job(seed: int, stop_s: float, num_hosts: int,
                     msgload: int) -> dict:
    """One fleet-smoke experiment config: the flagship PHOLD shape at a
    small host count (compile cost dominates solo runs at this scale,
    which is exactly the cost the fleet amortizes)."""
    from shadow_tpu.flagship import SELF_LOOP_50MS_GML

    return {
        "general": {"stop_time": f"{stop_s} s", "seed": seed},
        "network": {"graph": {"type": "gml", "inline": SELF_LOOP_50MS_GML}},
        "experimental": {
            "event_capacity": max(3 * num_hosts * msgload // 2, 4096),
            "events_per_host_per_window": msgload + 16,
            "outbox_slots": msgload + 16,
            "inbox_slots": 4,
        },
        "hosts": {
            "peer": {
                "quantity": num_hosts,
                "app_model": "phold",
                # runtime fixed across jobs (it compiles into the handler);
                # mixed LENGTH comes from general.stop_time
                "app_options": {"msgload": msgload, "runtime": 1},
            }
        },
    }


def stage_fleet_smoke(jobs: int = 8, num_hosts: int = 256, msgload: int = 2):
    """Fleet gate (ISSUE 4 acceptance): a fleet of `jobs` small mixed-
    length PHOLD experiments must compile ONE window kernel (asserted via
    the fleet's trace-count metric) and beat the summed wall-clock of the
    same experiments run solo — on CPU, where the win is compile/trace
    amortization plus dispatch batching."""
    import jax

    from shadow_tpu.fleet import JobSpec, build_fleet
    from shadow_tpu.sim import build_simulation

    stops = [1.5 + 0.5 * (i % 4) for i in range(jobs)]  # 1.5 .. 3.0 s
    cfgs = [
        _fleet_smoke_job(seed=100 + i, stop_s=stops[i],
                         num_hosts=num_hosts, msgload=msgload)
        for i in range(jobs)
    ]

    # solo arm: each experiment pays its own build + trace/compile + run
    solo_walls = []
    solo_events = []
    for cfg in cfgs:
        t0 = time.perf_counter()
        sim = build_simulation(cfg)
        sim.run()
        jax.block_until_ready(sim.state.pool.time)
        solo_walls.append(time.perf_counter() - t0)
        solo_events.append(sim.counters()["events_committed"])

    # fleet arm: one vmapped program, jobs swap through the lanes
    t0 = time.perf_counter()
    fleet = build_fleet(
        [JobSpec(name=f"smoke{i:02d}", config=cfgs[i]) for i in range(jobs)]
    )
    fleet.run()
    jax.block_until_ready(fleet.state.pool.time)
    fleet_wall = time.perf_counter() - t0

    rows = fleet.results()
    events_equal = [
        r["events_committed"] == e for r, e in zip(rows, solo_events)
    ]
    solo_sum = sum(solo_walls)
    traces = fleet.fleet_stats()["kernel_traces"]
    return {
        "stage": "fleet_smoke",
        "platform": jax.default_backend(),
        "jobs": jobs,
        "hosts": num_hosts,
        "stops_s": stops,
        "solo_wall_sum_s": round(solo_sum, 3),
        "fleet_wall_s": round(fleet_wall, 3),
        "speedup": round(solo_sum / fleet_wall, 2) if fleet_wall else 0.0,
        "kernel_traces": traces,
        "events_equal": all(events_equal),
        "jobs_done": fleet.fleet_stats()["jobs_done"],
        "gate_one_compile": traces == 1,
        "gate_wall": fleet_wall < solo_sum,
    }


def shard_sweep(shards=(1, 2, 4, 8), out_path: str | None = None):
    """Virtual-islands scaling sweep on ONE chip (VERDICT r4 gate 1c):
    PHOLD 16k and udp_flood_10k at each shard count; one JSON line each.
    Writes docs/shard_sweep.json for tools/plot_shards.py."""
    results = []
    for s in shards:
        ev, wall, spw = device_phold(16384, 8, 10, 64, s)
        r = {"stage": "phold_16k", "num_shards": s,
             "events_per_sec": round(ev / wall, 1),
             "sim_sec_per_wall_sec": round(spw, 2)}
        print(json.dumps(r), flush=True)
        results.append(r)
    for s in shards:
        r = _run_stage(
            "udp_flood_10k", "udp_flood", 0.001,
            {"interval": "20 ms", "size": 1024, "runtime": 3},
            num_hosts=10240, stop_s=4, event_capacity=1 << 15,
            extra_experimental={"events_per_host_per_window": 12,
                                "outbox_slots": 8},
            windows_per_dispatch=32, num_shards=s,
        )
        print(json.dumps(r), flush=True)
        results.append(r)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    return results


def stage_resilience_smoke(num_hosts: int = 1024, msgload: int = 2,
                           stop_s: int = 2):
    """Backend-survivability gate (ISSUE 6 acceptance): a deterministic
    `kill_backend` injection mid-run must (a) drain to a crash-consistent
    checkpoint whose resumed run ends on the uninterrupted run's exact
    audit digest chain, and (b) complete in-process under
    `--on-backend-loss cpu` with the same chain, with the failover's wall
    overhead recorded. Writes a schema-v6 metrics artifact carrying the
    resilience.* namespace (tools/validate_metrics.py gates it).
    CPU-deterministic by design (the injection IS the outage)."""
    import tempfile

    import jax

    from shadow_tpu.core.supervisor import BackendLost, BackendSupervisor
    from shadow_tpu.faults import plan as plan_mod
    from shadow_tpu.flagship import build_phold_flagship
    from shadow_tpu.obs import metrics as obs_metrics

    def build():
        return build_phold_flagship(
            num_hosts, msgload=msgload, stop_s=stop_s, runtime_s=stop_s,
        )

    kill_at = [{"at": "1 s", "op": "kill_backend"}]

    # uninterrupted baseline
    t0 = time.perf_counter()
    ref = build()
    ref.run(windows_per_dispatch=4)
    jax.block_until_ready(ref.state.pool.time)
    wall_base = time.perf_counter() - t0
    base_chain = ref.audit_chain()
    base_events = ref.counters()["events_committed"]

    with tempfile.TemporaryDirectory(prefix="resilience_smoke_") as td:
        # (a) kill mid-run under policy abort: drain, then resume
        sim = build()
        sim.checkpoint_dir = td
        sim.attach_supervisor(BackendSupervisor(policy="abort"))
        sim.attach_faults(plan_mod.parse_fault_plan(kill_at))
        drained = False
        try:
            sim.run(windows_per_dispatch=4)
        except BackendLost:
            drained = True
        resumed = build()
        resumed.resume_from(td)
        resumed.run(windows_per_dispatch=4)
        resume_chain_equal = (
            drained and resumed.audit_chain() == base_chain
            and resumed.counters()["events_committed"] == base_events
        )

    # (b) kill under policy cpu: degraded-mode failover completes the run
    t0 = time.perf_counter()
    sim = build()
    sup = BackendSupervisor(policy="cpu", recheck_every=4)
    sim.attach_supervisor(sup)
    sim.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "kill_backend", "recover_after": 1}]
    ))
    sim.run(windows_per_dispatch=4)
    jax.block_until_ready(sim.state.pool.time)
    wall_failover = time.perf_counter() - t0
    failover_chain_equal = sim.audit_chain() == base_chain
    rstats = sim.resilience_stats()

    metrics_path = os.path.join(_REPO, "resilience_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(sim)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "resilience_smoke", "hosts": num_hosts,
    })
    obs_metrics.validate_metrics_doc(doc)
    resilience_recorded = (
        doc["counters"].get("resilience.drains", 0) >= 1
        and doc["counters"].get("resilience.failovers", 0) >= 1
    )

    return {
        "stage": "resilience_smoke",
        "platform": jax.default_backend(),
        "hosts": num_hosts,
        "chain": int(base_chain),
        "wall_base_s": round(wall_base, 3),
        "wall_failover_s": round(wall_failover, 3),
        "failover_overhead_pct": round(
            (wall_failover - wall_base) / wall_base * 100.0, 2
        ) if wall_base > 0 else 0.0,
        "drained": drained,
        "resume_chain_equal": resume_chain_equal,
        "failover_chain_equal": failover_chain_equal,
        "resilience": {k: int(v) for k, v in sorted(rstats.items())},
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_resume": resume_chain_equal,
        "gate_failover": failover_chain_equal,
        "gate": bool(
            resume_chain_equal and failover_chain_equal
            and resilience_recorded
        ),
    }


def stage_pressure_smoke(num_hosts: int = 512, msgload: int = 4,
                         stop_s: int = 2):
    """Pressure-plane gate (ISSUE 9 acceptance): resource exhaustion must
    degrade deterministically instead of dying.

    (a) `exhaust_backend` mid-run: the classified RESOURCE_EXHAUSTED
        drives the degradation ladder (forced gear downshift overriding
        the red-zone rule, overflow parked on the host spill tier) and
        the run COMPLETES in-process with the uninterrupted run's exact
        audit digest chain.
    (b) the same injection with the ladder DISABLED reproduces the
        pre-ladder behavior: drain-to-checkpoint + a typed abort
        (BackendLost) — never a bare RuntimeError.
    (c) `saturate_pool` mid-window: sustained simulated pool pressure is
        absorbed by spill-tier escalation; the run completes with the
        exact chain where a stall used to raise.

    Writes a schema-v8 metrics artifact carrying the pressure.*
    namespace (tools/validate_metrics.py gates it).
    CPU-deterministic by design (the injections ARE the pressure)."""
    import jax

    from shadow_tpu.core.pressure import (
        PressureController, PressurePolicy,
    )
    from shadow_tpu.core.supervisor import BackendLost, BackendSupervisor
    from shadow_tpu.faults import plan as plan_mod
    from shadow_tpu.flagship import build_phold_flagship
    from shadow_tpu.obs import metrics as obs_metrics

    def build():
        # occupancy (H x msgload) lands the build at the TOP gear, so the
        # ladder's forced downshift has a smaller tier to retreat to
        return build_phold_flagship(
            num_hosts, msgload=msgload, stop_s=stop_s, runtime_s=stop_s,
            pool_gears=2,
        )

    def quiet_supervisor(policy="wait"):
        return BackendSupervisor(policy, sleep=lambda s: None)

    # uninterrupted baseline
    t0 = time.perf_counter()
    ref = build()
    ref.run(windows_per_dispatch=4)
    jax.block_until_ready(ref.state.pool.time)
    wall_base = time.perf_counter() - t0
    base_chain = ref.audit_chain()
    base_events = ref.counters()["events_committed"]

    exhaust_plan = [
        {"at": "1 s", "op": "exhaust_backend", "recover_after": 1}
    ]

    # (a) exhaust → ladder engages → completes with the exact chain
    t0 = time.perf_counter()
    sim = build()
    sim.attach_supervisor(quiet_supervisor())
    sim.attach_faults(plan_mod.parse_fault_plan(exhaust_plan))
    sim.run(windows_per_dispatch=4)
    jax.block_until_ready(sim.state.pool.time)
    wall_ladder = time.perf_counter() - t0
    pstats = sim.pressure_stats()
    ladder_engaged = (
        pstats.get("downshifts", 0) + pstats.get("spill_escalations", 0)
        >= 1
    )
    ladder_chain_equal = (
        sim.audit_chain() == base_chain
        and sim.counters()["events_committed"] == base_events
    )

    # (b) control arm — ladder disabled: the pre-ladder outcome, typed
    control = build()
    control.attach_pressure(
        PressureController(PressurePolicy(enabled=False))
    )
    control.attach_supervisor(quiet_supervisor(policy="abort"))
    control.attach_faults(plan_mod.parse_fault_plan(exhaust_plan))
    control_typed_abort = False
    try:
        control.run(windows_per_dispatch=4)
    except BackendLost:
        control_typed_abort = True

    # (c) saturate_pool → spill escalation absorbs it, chain identical
    sat = build()
    sat.attach_faults(plan_mod.parse_fault_plan(
        [{"at": "1 s", "op": "saturate_pool", "frac": 0.2}]
    ))
    sat.run(windows_per_dispatch=4)
    sat_chain_equal = (
        sat.audit_chain() == base_chain
        and sat.counters()["events_committed"] == base_events
    )
    sat_spilled = sat.spill_stats()["spill_episodes"] >= 1

    metrics_path = os.path.join(_REPO, "pressure_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(sim)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "pressure_smoke", "hosts": num_hosts,
    })
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    pressure_recorded = (
        doc["counters"].get("pressure.ladder_steps", 0) >= 1
        and "pressure.estimated_bytes" in doc["gauges"]
    )

    return {
        "stage": "pressure_smoke",
        "platform": jax.default_backend(),
        "hosts": num_hosts,
        "chain": int(base_chain),
        "wall_base_s": round(wall_base, 3),
        "wall_ladder_s": round(wall_ladder, 3),
        "pressure": {k: int(v) for k, v in sorted(pstats.items())},
        "ladder_chain_equal": ladder_chain_equal,
        "control_typed_abort": control_typed_abort,
        "saturate_chain_equal": sat_chain_equal,
        "saturate_spill_episodes": int(
            sat.spill_stats()["spill_episodes"]
        ),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_ladder": bool(ladder_engaged and ladder_chain_equal),
        "gate_control": bool(control_typed_abort),
        "gate_saturate": bool(sat_chain_equal and sat_spilled),
        "gate": bool(
            ladder_engaged and ladder_chain_equal and control_typed_abort
            and sat_chain_equal and sat_spilled and pressure_recorded
        ),
    }


def _async_smoke_gml(shards: int, per: int, seed: int = 7) -> str:
    """The async-smoke topology: one vertex per host; DECOHERED
    intra-shard latencies (events stop clustering on a shared lattice, so
    the barrier can't batch different shards' windows together) with
    shard 0 drawn from a faster band (the DELIBERATE imbalance: it needs
    ~2x the windows of any other shard and serializes the barrier
    driver); cross-shard latencies large and distinct — the generous
    lookahead that lets every other shard run its own windows
    concurrently instead of idling through shard 0's."""
    import numpy as np

    rng = np.random.RandomState(seed)
    n = shards * per

    def band(a: int, b: int) -> tuple[int, int]:
        if a // per != b // per:
            return 700000, 900000  # cross-shard: the generous lookahead
        return (5000, 120000) if a // per == 0 else (60000, 250000)

    lines = ["graph ["]
    for v in range(n):
        lines.append(f"  node [ id {v} ]")
    for a in range(n):
        for b in range(a, n):
            lo, hi = band(a, b)
            lines.append(
                f'  edge [ source {a} target {b} latency '
                f'"{int(rng.randint(lo, hi))} us" ]'
            )
    lines.append("]")
    return "\n".join(lines)


def stage_async_smoke(shards: int = 4, hosts_per_shard: int = 4,
                      stop_s: int = 30, span: int = 2):
    """Async conservative-sync gate (ISSUE 10 acceptance): a deliberately
    imbalanced islands workload (locality-biased PHOLD on a decohered
    topology whose shard 0 runs a ~2x faster event timescale) driven by
    the barrier loop vs the per-shard-frontier async loop
    (parallel/islands.make_shard_run_to_async). Gates:

      * async wall < barrier wall, with the mechanism pinned by the
        superstep ratio (async needs strictly fewer device-loop
        iterations — the barrier serializes the union of all shards'
        windows, async overlaps them);
      * the global audit digest chain is BIT-IDENTICAL to the barrier
        run's (and committed events equal) — asynchrony changed the
        schedule, never the simulation;
      * the schema-v9 metrics artifact records async.* and validates
        under --strict-namespaces.

    CPU-deterministic by design (both arms run the same CPU backend)."""
    import jax

    from shadow_tpu.core import simtime
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.sim import build_simulation

    gml = _async_smoke_gml(shards, hosts_per_shard)

    def cfg(async_on: bool) -> dict:
        hosts = {}
        for v in range(shards * hosts_per_shard):
            hosts[f"h{v:02d}"] = {
                "quantity": 1, "network_node_id": v, "app_model": "phold",
                "app_options": {
                    "msgload": 1, "runtime": stop_s - 1, "local_span": span,
                },
            }
        return {
            "general": {"stop_time": stop_s, "seed": 42},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "experimental": {
                "event_capacity": 2048, "events_per_host_per_window": 8,
                "outbox_slots": 8, "inbox_slots": 4,
                "num_shards": shards, "exchange_slots": 32,
                "async_islands": async_on,
            },
            "hosts": hosts,
        }

    def run_arm(async_on: bool):
        sim = build_simulation(cfg(async_on))
        # warm through compile + the aligned start burst, then time the
        # steady decohered region
        sim.run(until=2 * simtime.NS_PER_SEC, windows_per_dispatch=4096)
        jax.block_until_ready(sim.state.pool.time)
        t0 = time.perf_counter()
        sim.run(windows_per_dispatch=4096)
        jax.block_until_ready(sim.state.pool.time)
        return sim, time.perf_counter() - t0

    # interleave arms to decorrelate machine drift from the comparison
    barrier, w_b = run_arm(False)
    async_sim, w_a = run_arm(True)
    w_b = min(w_b, run_arm(False)[1])
    w_a = min(w_a, run_arm(True)[1])

    chain_equal = barrier.audit_chain() == async_sim.audit_chain()
    ev_b = barrier.counters()["events_committed"]
    ev_a = async_sim.counters()["events_committed"]
    steps_b, steps_a = barrier.windows_run, async_sim.windows_run
    astats = async_sim.async_stats() or {}

    metrics_path = os.path.join(_REPO, "async_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(async_sim)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "async_smoke", "hosts": shards * hosts_per_shard,
        "shards": shards,
    })
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    async_recorded = (
        doc["counters"].get("async.supersteps", 0) > 0
        and "async.frontier_spread_max_ns" in doc["gauges"]
    )

    gate_wall = w_a < w_b
    gate_steps = steps_a < steps_b
    gate_chain = bool(chain_equal and ev_a == ev_b)
    return {
        "stage": "async_smoke",
        "platform": jax.default_backend(),
        "hosts": shards * hosts_per_shard,
        "shards": shards,
        "events": int(ev_a),
        "events_equal": ev_a == ev_b,
        "chain": int(async_sim.audit_chain()),
        "chain_equal": chain_equal,
        "supersteps_barrier": int(steps_b),
        "supersteps_async": int(steps_a),
        "superstep_ratio": round(steps_b / max(steps_a, 1), 2),
        "wall_barrier_s": round(w_b, 3),
        "wall_async_s": round(w_a, 3),
        "wall_ratio": round(w_b / w_a, 2) if w_a else 0.0,
        "async": {k: int(v) for k, v in sorted(astats.items())},
        "frontier_spread_max_ns": int(
            doc["gauges"].get("async.frontier_spread_max_ns", -1)
        ),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_wall": gate_wall,
        "gate_supersteps": gate_steps,
        "gate_chain": gate_chain,
        "gate": bool(
            gate_wall and gate_steps and gate_chain and async_recorded
        ),
    }


def stage_profile_smoke(shards: int = 4, hosts_per_shard: int = 4,
                        stop_s: int = 30, span: int = 2,
                        overhead_tol: float = 0.03):
    """shadowscope gate (ISSUE 20 acceptance): the profiling plane is
    observation, never participation. On the async-smoke workload (same
    topology/seed — shard 0 is the deliberately skewed hot shard):

      * profiler-on vs profiler-off runs keep BIT-IDENTICAL audit chains
        and equal committed events — the recorder is read-only against
        the sim;
      * profiler overhead <= 3% wall (min-of-2 per arm, interleaved);
      * critical-path attribution names shard 0 (the hot-frac shard the
        topology skews) from the recorded per-shard frontier intervals;
      * merging two runs' profile docs folds histograms EXACTLY (merged
        counts/sums equal the per-peer sums — the router /timez
        invariant);
      * the profile doc validates, and the schema-current metrics
        artifact carries prof.* keys under --strict-namespaces.

    Both arms run the same CPU backend."""
    import jax

    from shadow_tpu.core import simtime
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.obs import prof as obs_prof
    from shadow_tpu.obs.hist import LogHistogram
    from shadow_tpu.sim import build_simulation

    gml = _async_smoke_gml(shards, hosts_per_shard)
    hosts = {}
    for v in range(shards * hosts_per_shard):
        hosts[f"h{v:02d}"] = {
            "quantity": 1, "network_node_id": v, "app_model": "phold",
            "app_options": {
                "msgload": 1, "runtime": stop_s - 1, "local_span": span,
            },
        }
    cfg = {
        "general": {"stop_time": stop_s, "seed": 42},
        "network": {"graph": {"type": "gml", "inline": gml}},
        "experimental": {
            "event_capacity": 2048, "events_per_host_per_window": 8,
            "outbox_slots": 8, "inbox_slots": 4,
            "num_shards": shards, "exchange_slots": 32,
            "async_islands": True,
        },
        "hosts": hosts,
    }

    def run_arm(profiled: bool):
        sim = build_simulation(cfg)
        prof = None
        if profiled:
            prof = obs_prof.ProfRecorder()
            sim.obs_session = obs_metrics.ObsSession(prof=prof)
        # small dispatches so every handoff boundary lands an interval
        # in the ring (the barrier-free loop still overlaps shards)
        sim.run(until=2 * simtime.NS_PER_SEC, windows_per_dispatch=64)
        jax.block_until_ready(sim.state.pool.time)
        t0 = time.perf_counter()
        sim.run(windows_per_dispatch=64)
        jax.block_until_ready(sim.state.pool.time)
        return sim, prof, time.perf_counter() - t0

    # interleave arms to decorrelate machine drift from the comparison
    off_sim, _, w_off = run_arm(False)
    on_sim, prof_a, w_on = run_arm(True)
    w_off = min(w_off, run_arm(False)[2])
    on2_sim, prof_b, w_on2 = run_arm(True)
    w_on = min(w_on, w_on2)

    chain_equal = off_sim.audit_chain() == on_sim.audit_chain()
    ev_off = off_sim.counters()["events_committed"]
    ev_on = on_sim.counters()["events_committed"]
    overhead = (w_on - w_off) / w_off if w_off > 0 else 0.0

    doc_a = prof_a.to_doc(meta={"peer": "a"})
    doc_b = prof_b.to_doc(meta={"peer": "b"})
    obs_prof.validate_profile_doc(doc_a)
    obs_prof.validate_profile_doc(doc_b)
    cp = obs_prof.critical_path(doc_a)

    # the federation /timez invariant: merged histograms ARE the sums
    merged = obs_prof.merge_profile_docs({"a": doc_a, "b": doc_b})
    merge_exact = True
    for name in set(doc_a["hists"]) | set(doc_b["hists"]):
        ha = LogHistogram.from_doc(
            doc_a["hists"][name]) if name in doc_a["hists"] \
            else LogHistogram()
        hb = LogHistogram.from_doc(
            doc_b["hists"][name]) if name in doc_b["hists"] \
            else LogHistogram()
        hm = LogHistogram.from_doc(merged["hists"][name])
        if hm.count != ha.count + hb.count \
                or hm.sum != ha.sum + hb.sum:
            merge_exact = False

    gate_chain = bool(chain_equal and ev_on == ev_off)
    gate_overhead = overhead <= overhead_tol
    gate_critical = cp is not None and cp["critical_shard"] == 0
    gate_recorded = prof_a.recorded > 0 and bool(doc_a["hists"])

    gate = bool(
        gate_chain and gate_overhead and gate_critical
        and gate_recorded and merge_exact
    )
    metrics_path = os.path.join(_REPO, "profile_smoke.metrics.json")
    session = on_sim.obs_session  # carries the run's spans + prof_a
    session.finalize(on_sim)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "profile_smoke", "hosts": shards * hosts_per_shard,
        "shards": shards, "wall_s": round(w_on, 3), "ok": gate,
    })
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    prof_recorded = (
        doc["counters"].get("prof.intervals", 0) > 0
        and "prof.critical_shard" in doc["gauges"]
    )
    return {
        "stage": "profile_smoke",
        "platform": jax.default_backend(),
        "hosts": shards * hosts_per_shard,
        "shards": shards,
        "events": int(ev_on),
        "chain_equal": chain_equal,
        "wall_off_s": round(w_off, 3),
        "wall_on_s": round(w_on, 3),
        "overhead_frac": round(overhead, 4),
        "intervals": int(prof_a.recorded),
        "dropped": int(prof_a.dropped),
        "critical_shard": None if cp is None else cp["critical_shard"],
        "critical_wall_frac": None if cp is None
        else round(cp["wall_frac"], 3),
        "blocked_frac": None if cp is None
        else round(cp["blocked_frac"], 3),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_chain": gate_chain,
        "gate_overhead": gate_overhead,
        "gate_critical": gate_critical,
        "gate_merge": merge_exact,
        "gate_recorded": bool(gate_recorded and prof_recorded),
        "gate": bool(gate and prof_recorded),
    }


def _balance_smoke_gml(shards: int, per: int, seed: int = 7) -> str:
    """The balance-smoke topology: one vertex per host, decohered
    UNIFORM intra-shard latency bands (no structurally fast shard — the
    hotness must come from the `skew_hosts` injection, not the graph)
    and large distinct cross-shard latencies (generous lookahead, so the
    only thing that throttles the healthy shards is a laggard's
    frontier)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    n = shards * per

    def band(a: int, b: int) -> tuple[int, int]:
        if a // per != b // per:
            return 700000, 900000
        return 30000, 250000

    lines = ["graph ["]
    for v in range(n):
        lines.append(f"  node [ id {v} ]")
    for a in range(n):
        for b in range(a, n):
            lo, hi = band(a, b)
            lines.append(
                f'  edge [ source {a} target {b} latency '
                f'"{int(rng.randint(lo, hi))} us" ]'
            )
    lines.append("]")
    return "\n".join(lines)


def stage_balance_smoke(shards: int = 4, per: int = 4, stop_s: int = 10,
                        skew_at_s: int = 2, settle_s: int = 4):
    """Self-balancing fleet gate (ISSUE 11 acceptance): a hot-shard
    workload DRIVEN by a `skew_hosts` injection — destination-biased
    PHOLD (half of all traffic targets shard 0's hosts) whose pending
    events are replicated 6x at t=2s — run three ways:

      control   balancer off: shard 0 stays the chronic frontier
                laggard for the rest of the run;
      balanced  balancer on: the hot shard is detected (occupancy +
                laggard hysteresis), the assignment refined by min-cut,
                and hosts migrated live through the traced-lookahead
                seam;
      rollback  balancer on with a FORCED mid-migration failure on the
                first attempt (ShardBalancer.inject_failure_next): the
                move must roll back to the pre-move layout + cooldown.

    Gates: the balanced arm shows LOWER post-settle frontier spread and
    FEWER blocked_on_neighbor supersteps than control; all three arms'
    audit digest chains are BIT-IDENTICAL (migrations and rollbacks
    change the schedule, never the simulation); at least one migration
    committed and the rollback arm rolled back; the balanced run is
    retrace-free (migrations never recompile — hlo_audit.retrace_report
    gate); and the schema-v10 metrics artifact records balance.* and
    validates under --strict-namespaces. CPU-deterministic by design."""
    import jax

    from shadow_tpu.analysis import hlo_audit
    from shadow_tpu.core import simtime
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.sim import build_simulation

    gml = _balance_smoke_gml(shards, per)
    n = shards * per

    def cfg(balancer: bool) -> dict:
        hosts = {}
        for v in range(n):
            hosts[f"h{v:02d}"] = {
                "quantity": 1, "network_node_id": v, "app_model": "phold",
                "app_options": {
                    "msgload": 2, "runtime": stop_s - 1,
                    # persistent destination bias: half of ALL forwards
                    # target shard 0's hosts, so the skew_hosts
                    # amplification keeps re-concentrating there until
                    # (unless) the balancer spreads those hosts out
                    "hot_frac": per / n, "hot_share": 0.5,
                },
            }
        return {
            "general": {"stop_time": stop_s, "seed": 42},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "experimental": {
                "event_capacity": 4096, "events_per_host_per_window": 8,
                "outbox_slots": 8, "inbox_slots": 4,
                "num_shards": shards, "exchange_slots": 32,
                "rebalance": True,  # control arm compiles the same
                # slot_of-routing kernel, so the comparison is balancer
                # policy only, never kernel shape
                "balancer": balancer,
                "balance_streak": 3, "balance_cooldown": 8,
                "balance_hot_ratio": 1.5,
            },
            "hosts": hosts,
            "faults": {"inject": [{
                "at": f"{skew_at_s} s", "op": "skew_hosts",
                "span": [0, per], "factor": 6,
            }]},
        }

    settle_ns = (skew_at_s + settle_s) * simtime.NS_PER_SEC

    def run_arm(mode: str):
        sim = build_simulation(cfg(mode != "control"))
        sim.attach_faults(sim.config.faults.load_faults())
        if mode == "rollback":
            sim.balancer.inject_failure_next()
        # phase 1: pre-skew + skew + the balancer's detection/migration
        # window; phase 2 (post-settle) is what the gates measure
        sim.run(until=settle_ns, windows_per_dispatch=16)
        blocked0 = (sim.async_stats() or {}).get("blocked_on_neighbor", 0)
        sim.reset_frontier_spread()
        sim.run(windows_per_dispatch=16)
        blocked2 = (
            (sim.async_stats() or {}).get("blocked_on_neighbor", 0)
            - blocked0
        )
        spread2 = (sim.async_gauges() or {}).get(
            "frontier_spread_max_ns", -1
        )
        return sim, blocked2, spread2

    control, blocked_c, spread_c = run_arm("control")
    balanced, blocked_b, spread_b = run_arm("balanced")
    rollback, blocked_r, _ = run_arm("rollback")

    chain = balanced.audit_chain()
    chains_equal = (
        chain == control.audit_chain() == rollback.audit_chain()
    )
    ev = balanced.counters()["events_committed"]
    events_equal = (
        ev == control.counters()["events_committed"]
        == rollback.counters()["events_committed"]
    )
    bstats = balanced.balance_stats() or {}
    rstats = rollback.balance_stats() or {}
    retrace = hlo_audit.retrace_report(balanced)

    metrics_path = os.path.join(_REPO, "balance_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(balanced)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "balance_smoke", "hosts": n, "shards": shards,
    })
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    balance_recorded = (
        doc["counters"].get("balance.migrations", 0) > 0
        and "balance.state" in doc["gauges"]
    )

    gate_blocked = blocked_b < blocked_c
    gate_spread = 0 <= spread_b < spread_c
    gate_chain = bool(chains_equal and events_equal)
    gate_heal = bstats.get("migrations", 0) >= 1
    gate_rollback = rstats.get("rollbacks", 0) >= 1
    return {
        "stage": "balance_smoke",
        "platform": jax.default_backend(),
        "hosts": n,
        "shards": shards,
        "events": int(ev),
        "chain": int(chain),
        "chain_equal": bool(chains_equal),
        "events_equal": bool(events_equal),
        "skewed_rows": int(
            balanced.fault_stats().get("events_skewed", 0)
        ),
        "migrations": int(bstats.get("migrations", 0)),
        "hosts_moved": int(bstats.get("hosts_moved", 0)),
        "rollbacks_in_rollback_arm": int(rstats.get("rollbacks", 0)),
        "blocked_control": int(blocked_c),
        "blocked_balanced": int(blocked_b),
        "blocked_rollback_arm": int(blocked_r),
        "spread_control_ns": int(spread_c),
        "spread_balanced_ns": int(spread_b),
        "shard_loads_control": [int(x) for x in control.shard_loads()],
        "shard_loads_balanced": [int(x) for x in balanced.shard_loads()],
        "retrace_ok": bool(retrace["ok"]),
        "kernel_compiles": int(retrace["compiles_total"]),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_blocked": bool(gate_blocked),
        "gate_spread": bool(gate_spread),
        "gate_chain": gate_chain,
        "gate_heal": bool(gate_heal),
        "gate_rollback": bool(gate_rollback),
        "gate": bool(
            gate_blocked and gate_spread and gate_chain and gate_heal
            and gate_rollback and retrace["ok"] and balance_recorded
        ),
    }


def _mesh_smoke_gml(hosts: int, comm: int, offset: int, span: int,
                    seed: int = 7) -> str:
    """The mesh-smoke topology: one vertex per host on a ring,
    DIRECT-EDGE routing only (use_shortest_path false, so the in-edge
    matrix is genuinely sparse — shortest-path baking would make every
    shard pair adjacent and reduce ppermute to a ring all_gather).
    Hosts within ring distance <= span are connected; COMMUNITIES of
    `comm` contiguous hosts, offset by `offset` from the chip-block
    boundaries, get fast decohered intra links (the chatty pairs) while
    community-crossing links are ~15x slower. The block partition
    therefore splits every community across two chips — its min cross-
    chip lookahead is the FAST band, so neighbor blocking is chronic —
    while the min-cut placement re-aligns chips onto communities and
    only the slow boundary links cross."""
    import numpy as np

    rng = np.random.RandomState(seed)
    lines = ["graph ["]
    for v in range(hosts):
        lines.append(f"  node [ id {v} ]")
    for a in range(hosts):
        lines.append(
            f'  edge [ source {a} target {a} latency '
            f'"{int(rng.randint(2000, 3000))} us" ]'
        )
        for d in range(1, span + 1):
            b = (a + d) % hosts
            same = ((a - offset) % hosts) // comm == (
                (b - offset) % hosts) // comm
            lo, hi = (3000, 6000) if same else (45000, 60000)
            lines.append(
                f'  edge [ source {a} target {b} latency '
                f'"{int(rng.randint(lo, hi))} us" ]'
            )
    lines.append("]")
    return "\n".join(lines)


def stage_mesh_smoke(shards: int = 8, per: int = 4, stop_s: int = 8,
                     span: int = 3):
    """True multi-chip gate (ISSUE 12 acceptance): the fused async
    islands driver runs as `shard_map` over an 8-chip virtual CPU mesh
    with per-chip state placement and NEIGHBOR-ONLY ppermute frontier
    exchange, against two references:

      vmap      the single-program islands run (one chip, virtual
                shards) — the bit-identity reference;
      gather    shard_map with the all_gather frontier exchange and the
                block placement — the collective-volume/blocking
                comparison arm.

    Gates: all three audit digest chains BIT-IDENTICAL (mesh execution
    changes where state lives, never the simulation); ZERO all-gather
    ops in the optimized HLO of the mesh kernel's frontier exchange
    (hlo_audit.all_gather_lines; the control arm shows >0); cross-chip
    collective volume scales with in-edge degree — the ppermute arm's
    analytic frontier-exchange bytes AND blocked-on-neighbor supersteps
    both land strictly below the gather arm's (min-cut placement keeps
    the fast community links intra-chip, so horizons are bounded by the
    slow boundary links only); and the mesh arm is RETRACE-FREE across
    a mid-run gear shift and a live host migration (retrace_report ok,
    zero exchange-schedule rebuilds). Writes the schema-v11 mesh.*
    metrics artifact, strict-namespace-validated. CPU-deterministic by
    design (all arms share one backend)."""
    import numpy as np

    import jax

    from shadow_tpu.analysis import hlo_audit
    from shadow_tpu.core import simtime
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.sim import build_simulation

    n = shards * per
    comm = per  # community size = chip size, offset so blocks split them
    offset = per // 2
    gml = _mesh_smoke_gml(n, comm, offset, span)

    def cfg(mode: str, exchange: str, placement: str,
            pool_gears: int = 1) -> dict:
        hosts = {}
        for v in range(n):
            hosts[f"h{v:02d}"] = {
                "quantity": 1, "network_node_id": v, "app_model": "phold",
                "app_options": {
                    "msgload": 1, "runtime": stop_s - 1, "local_span": span,
                },
            }
        return {
            "general": {"stop_time": stop_s, "seed": 42},
            "network": {
                "graph": {"type": "gml", "inline": gml},
                "use_shortest_path": False,
            },
            "experimental": {
                "event_capacity": 4096, "events_per_host_per_window": 8,
                "outbox_slots": 8, "inbox_slots": 4,
                "num_shards": shards, "exchange_slots": 16,
                "island_mode": mode, "mesh_exchange": exchange,
                "placement": placement, "pool_gears": pool_gears,
                "rebalance": True,
            },
            "hosts": hosts,
        }

    def boundary_swap(sim) -> None:
        """One live migration that PRESERVES shard-level connectivity:
        swap a boundary host pair between chips 0 and 1 chosen so every
        in-edge of the swapped layout still rides the compiled ppermute
        schedule (exchange_rebuilds must stay 0) — exactly the kind of
        move the balancer's cut-aware refinement prefers. Deterministic:
        first covered (a, b) pair in slot order."""
        from shadow_tpu.parallel import lookahead as lookahead_mod

        slot0 = np.asarray(jax.device_get(sim.params.slot_of))
        Hl = n // shards
        host_at = np.empty(n, np.int64)
        host_at[slot0] = np.arange(n)
        for sa in range(Hl):
            for sb in range(Hl, 2 * Hl):
                a, b = int(host_at[sa]), int(host_at[sb])
                slot = slot0.copy()
                slot[a], slot[b] = slot[b], slot[a]
                spec = lookahead_mod.derive(
                    sim._latency_np, sim._host_vertex_g, shards,
                    assignment=slot,
                )
                if lookahead_mod.shifts_covered(
                    spec, sim._async_shifts
                ):
                    sim.migrate_hosts(slot)
                    return
        raise RuntimeError(
            "mesh smoke: no connectivity-preserving boundary swap exists"
        )

    t0 = time.perf_counter()
    ref = build_simulation(cfg("vmap", "ppermute", "block"))
    ref.run(windows_per_dispatch=512)
    chain_ref = ref.audit_chain()
    ev_ref = ref.counters()["events_committed"]

    gather = build_simulation(cfg("shard_map", "all_gather", "block"))
    gather.run(windows_per_dispatch=512)

    mesh = build_simulation(
        cfg("shard_map", "ppermute", "min_cut", pool_gears=2)
    )
    # first leg, then a forced gear round-trip + a live migration at the
    # dispatch boundary — the retrace-freedom chaos the gate requires
    mesh.run(until=2 * simtime.NS_PER_SEC, windows_per_dispatch=512)
    top = mesh._gear_ladder[-1].level
    if top > 0:  # forced round-trip: both tiers' kernels run this smoke
        other = top - 1 if mesh._gear == top else top
        here = mesh._gear
        mesh._shift_gear(other)
        mesh._shift_gear(here)
    boundary_swap(mesh)
    mesh.run(windows_per_dispatch=512)

    chain_equal = (
        mesh.audit_chain() == chain_ref
        and gather.audit_chain() == chain_ref
    )
    ev_mesh = mesh.counters()["events_committed"]
    ev_gather = gather.counters()["events_committed"]

    # HLO gate: the mesh kernel's frontier exchange compiles to
    # collective-permutes only; the gather arm is the positive control
    def async_hlo(sim):
        fn = sim._gear_fns[sim._gear]["run_to_async"]
        return fn.lower(
            sim.state, sim.params, sim._async_runahead,
            sim._async_look_in, sim._async_spread,
            hlo_audit.DEFAULT_WIN_END, 8,
        ).compile().as_text()

    mesh_ag = len(hlo_audit.all_gather_lines(async_hlo(mesh)))
    control_ag = len(hlo_audit.all_gather_lines(async_hlo(gather)))
    retrace = hlo_audit.retrace_report(mesh)

    mstats = mesh.mesh_stats() or {}
    gstats = gather.mesh_stats() or {}
    bytes_mesh = mstats.get("frontier_exchange_bytes", 0)
    bytes_gather = gstats.get("frontier_exchange_bytes", 0)
    blocked_mesh = (mesh.async_stats() or {}).get("blocked_on_neighbor", 0)
    blocked_gather = (gather.async_stats() or {}).get(
        "blocked_on_neighbor", 0)

    metrics_path = os.path.join(_REPO, "mesh_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(mesh)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "mesh_smoke", "hosts": n, "chips": shards,
    })
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    mesh_recorded = (
        doc["counters"].get("mesh.frontier_exchange_bytes", 0) > 0
        and doc["gauges"].get("mesh.shard_map") == 1
        and "mesh.events_per_chip_max" in doc["gauges"]
    )

    gate_chain = bool(
        chain_equal and ev_mesh == ev_ref and ev_gather == ev_ref
    )
    gate_no_all_gather = mesh_ag == 0 and control_ag > 0
    gate_volume = bytes_mesh < bytes_gather
    gate_blocked = blocked_mesh < blocked_gather
    gate_retrace = bool(
        retrace["ok"] and mstats.get("exchange_rebuilds", 0) == 0
    )
    return {
        "stage": "mesh_smoke",
        "platform": jax.default_backend(),
        "devices": len(jax.devices()),
        "hosts": n,
        "chips": shards,
        "events": int(ev_mesh),
        "chain": int(mesh.audit_chain()),
        "chain_equal": chain_equal,
        "exchange_partners": int(mesh.exchange_partners),
        "in_degree_max": int(
            doc["gauges"].get("mesh.in_degree_max", -1)),
        "all_gathers_mesh": int(mesh_ag),
        "all_gathers_control": int(control_ag),
        "frontier_bytes_mesh": int(bytes_mesh),
        "frontier_bytes_gather": int(bytes_gather),
        "volume_ratio": round(bytes_gather / max(bytes_mesh, 1), 2),
        "blocked_mesh": int(blocked_mesh),
        "blocked_gather": int(blocked_gather),
        "migrations": int(mesh.rebalances),
        "gear_shifts": int(mesh._gear_shifts),
        "exchange_rebuilds": int(mstats.get("exchange_rebuilds", -1)),
        "cut_cost": doc["gauges"].get("mesh.cut_cost"),
        "cut_cost_block": doc["gauges"].get("mesh.cut_cost_block"),
        "kernel_compiles": int(retrace["compiles_total"]),
        "retraced": {k: int(v) for k, v in retrace["retraced"].items()},
        "wall_s": round(time.perf_counter() - t0, 3),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_chain": gate_chain,
        "gate_no_all_gather": bool(gate_no_all_gather),
        "gate_volume": bool(gate_volume),
        "gate_blocked": bool(gate_blocked),
        "gate_retrace": gate_retrace,
        "gate": bool(
            gate_chain and gate_no_all_gather and gate_volume
            and gate_blocked and gate_retrace and mesh_recorded
        ),
    }


def stage_mesh_resilience_smoke(shards: int = 8, per: int = 7,
                                stop_s: int = 6, span: int = 3):
    """Elastic mesh resilience gate (ISSUE 13 acceptance): a kill_chip
    mid-run on an 8-chip virtual CPU mesh drains to a checkpoint,
    relayouts onto the 7 surviving chips, CONTINUES, and — once the
    chip answers probes again — re-expands back to 8 at a dispatch
    boundary (parallel/elastic.py). Four arms:

      control   the uninterrupted 8-chip shard_map run — the chain
                reference;
      elastic   kill_chip {at 2s, chip 3, recovers} under policy
                `relayout`: drain → 7-chip relayout → re-expand → finish;
      wait      the same kill_chip under policy `wait` (hot resume on
                the full mesh once the chip answers) — the control arm
                proving relayout adds nothing the chain can see;
      shrink1   a 2-chip mesh losing one chip falls back to the GLOBAL
                engine (islands.globalize_state), the S→1 endpoint.

    Gates: every arm's audit chain and committed-event total BIT-
    IDENTICAL to its uninterrupted reference; exactly one counted
    kernel rebuild per mesh change (relayouts + re_expansions ==
    kernel_rebuilds − 1, and the re-expanded sim is retrace-free);
    ZERO all-gathers in the final mesh kernel's optimized HLO (the PR 12
    pin, unchanged by the elastic plane); drain checkpoints live in the
    drain-* namespace with the periodic ring intact; and the schema-v12
    mesh.* artifact strict-validates with the relayout counters
    recorded. CPU-deterministic (the injection is the outage, probes
    are countdown-driven)."""
    import tempfile

    import numpy as np  # noqa: F401 — config helpers below use jax only

    import jax

    from shadow_tpu.analysis import hlo_audit
    from shadow_tpu.core import checkpoint as ckpt_mod
    from shadow_tpu.core.supervisor import BackendSupervisor
    from shadow_tpu.faults import plan as plan_mod
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.parallel import elastic as elastic_mod
    from shadow_tpu.sim import build_simulation

    n = shards * per
    comm = per
    offset = per // 2
    gml = _mesh_smoke_gml(n, comm, offset, span)

    def cfg(hosts_n: int, chips: int, graph: str, stop: int) -> dict:
        hosts = {}
        for v in range(hosts_n):
            hosts[f"h{v:02d}"] = {
                "quantity": 1, "network_node_id": v, "app_model": "phold",
                "app_options": {
                    "msgload": 1, "runtime": stop - 1, "local_span": span,
                },
            }
        return {
            "general": {"stop_time": stop, "seed": 42},
            "network": {
                "graph": {"type": "gml", "inline": graph},
                "use_shortest_path": False,
            },
            "experimental": {
                "event_capacity": 8192, "events_per_host_per_window": 8,
                "outbox_slots": 8, "inbox_slots": 4,
                "num_shards": chips, "exchange_slots": 16,
                "island_mode": "shard_map",
            },
            "hosts": hosts,
        }

    def quiet_sup(policy):
        return BackendSupervisor(policy, sleep=lambda s: None,
                                 probe_budget_s=60.0)

    kill = [{"at": "2 s", "op": "kill_chip", "chip": 3,
             "recover_after": 2}]

    t0 = time.perf_counter()
    # --- control: uninterrupted 8-chip mesh ---
    base = cfg(n, shards, gml, stop_s)
    control = build_simulation(base)
    control.run(windows_per_dispatch=64)
    chain_ref = control.audit_chain()
    ev_ref = control.counters()["events_committed"]

    # --- elastic arm: kill → drain → relayout(7) → re-expand(8) ---
    with tempfile.TemporaryDirectory(prefix="mesh_resilience_") as td:
        runner = elastic_mod.ElasticMeshRunner(
            elastic_mod.config_builder(base), chips=shards, ckpt_dir=td,
            supervisor=quiet_sup("relayout"),
            faults=plan_mod.parse_fault_plan(kill),
            probe_every=1, hysteresis=2, cooldown=1,
            windows_per_dispatch=32,
        )
        mesh = runner.run()
        chain_elastic = mesh.audit_chain()
        ev_elastic = mesh.counters()["events_committed"]
        rstats = runner.stats()
        # drain-namespace satellite: the drains never touched the
        # periodic ring's namespace
        drains = ckpt_mod.ring_entries(td, prefix="drain")
        gate_drain_ns = len(drains) >= 2  # chip loss + re-expand

        # metrics artifact (schema v12, strict namespaces)
        metrics_path = os.path.join(
            _REPO, "mesh_resilience_smoke.metrics.json"
        )
        session = obs_metrics.ObsSession()
        session.finalize(mesh)
        doc = session.metrics.dump(metrics_path, meta={
            "stage": "mesh_resilience_smoke", "hosts": n, "chips": shards,
        })
        obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
        v12_recorded = (
            doc["schema_version"] == obs_metrics.SCHEMA_VERSION
            and doc["counters"].get("mesh.relayouts") == 1
            and doc["counters"].get("mesh.re_expansions") == 1
            and doc["counters"].get("mesh.chips_lost") == 1
            and doc["counters"].get("resilience.chip_losses", 0) >= 1
            and doc["gauges"].get("mesh.chips_up") == shards
            and doc["gauges"].get("mesh.chips_total") == shards
        )

        # the PR 12 hlo pin, unchanged: the re-expanded mesh kernel's
        # frontier exchange still lowers to collective-permutes only
        fn = mesh._gear_fns[mesh._gear]["run_to_async"]
        hlo = fn.lower(
            mesh.state, mesh.params, mesh._async_runahead,
            mesh._async_look_in, mesh._async_spread,
            hlo_audit.DEFAULT_WIN_END, 8,
        ).compile().as_text()
        mesh_ag = len(hlo_audit.all_gather_lines(hlo))
        retrace = hlo_audit.retrace_report(mesh)

    # --- wait-policy control arm: hot resume on the full mesh ---
    waits = build_simulation(base)
    waits.attach_supervisor(quiet_sup("wait"))
    waits.attach_faults(plan_mod.parse_fault_plan(kill))
    waits.run(windows_per_dispatch=32)
    chain_wait = waits.audit_chain()
    ev_wait = waits.counters()["events_committed"]

    # --- shrink-to-1 arm: 2 chips → 1 falls back to the global engine ---
    n1 = 2 * per
    gml1 = _mesh_smoke_gml(n1, per, per // 2, span)
    base1 = cfg(n1, 2, gml1, stop_s - 2)
    ref1 = build_simulation(base1)
    ref1.run(windows_per_dispatch=64)
    with tempfile.TemporaryDirectory(prefix="mesh_shrink1_") as td:
        runner1 = elastic_mod.ElasticMeshRunner(
            elastic_mod.config_builder(base1), chips=2, ckpt_dir=td,
            supervisor=quiet_sup("relayout"),
            faults=plan_mod.parse_fault_plan(
                [{"at": "1 s", "op": "kill_chip", "chip": 1}]
            ),
            windows_per_dispatch=32,
        )
        shrunk = runner1.run()
        gate_shrink1 = (
            shrunk.audit_chain() == ref1.audit_chain()
            and shrunk.counters()["events_committed"]
            == ref1.counters()["events_committed"]
            and not hasattr(shrunk, "num_shards")  # the global engine
        )

    gate_chain = bool(
        chain_elastic == chain_ref and ev_elastic == ev_ref
        and chain_wait == chain_ref and ev_wait == ev_ref
    )
    gate_elastic = (
        rstats["relayouts"] == 1 and rstats["re_expansions"] == 1
    )
    # one counted kernel rebuild per mesh change: the initial build plus
    # exactly one per relayout/re-expansion, and the final sim retraces
    # nothing on top of its own build
    gate_rebuilds = (
        rstats["kernel_rebuilds"] - 1
        == rstats["relayouts"] + rstats["re_expansions"]
        and retrace["ok"]
    )
    gate_hlo = mesh_ag == 0
    return {
        "stage": "mesh_resilience_smoke",
        "platform": jax.default_backend(),
        "devices": len(jax.devices()),
        "hosts": n,
        "chips": shards,
        "events": int(ev_elastic),
        "chain": int(chain_elastic),
        "relayouts": int(rstats["relayouts"]),
        "re_expansions": int(rstats["re_expansions"]),
        "chips_lost": int(rstats["chips_lost"]),
        "kernel_rebuilds": int(rstats["kernel_rebuilds"]),
        "relayout_downtime_ms": round(
            rstats["relayout_downtime_ns"] / 1e6, 1
        ),
        "drain_checkpoints": len(drains),
        "all_gathers_mesh": int(mesh_ag),
        "wall_s": round(time.perf_counter() - t0, 3),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_chain": gate_chain,
        "gate_elastic": bool(gate_elastic),
        "gate_rebuilds": bool(gate_rebuilds),
        "gate_hlo": bool(gate_hlo),
        "gate_shrink1": bool(gate_shrink1),
        "gate_drain_namespace": bool(gate_drain_ns),
        "gate_v12": bool(v12_recorded),
        "gate": bool(
            gate_chain and gate_elastic and gate_rebuilds and gate_hlo
            and gate_shrink1 and gate_drain_ns and v12_recorded
        ),
    }


_SERVE_SMOKE_SWEEP = {
    "sweep": {
        "name": "serve-smoke",
        "lanes": 2,
        "matrix": {
            "general.seed": [11, 12, 13, 14],
            "general.stop_time": ["900 ms", "1.4 s"],
        },
    },
    "fleet": {"windows_per_dispatch": 2},
}


def stage_serve_smoke(num_hosts: int = 64, msgload: int = 2):
    """Sim-as-a-service gate (ISSUE 8 acceptance): submit a sweep to the
    daemon, SIGKILL it mid-sweep, restart it with the same state dir,
    and require (a) the journal-replayed sweep to finish with per-job
    audit digest chains bit-identical (and identically ordered) to an
    uninterrupted in-process fleet run, and (b) the restarted daemon to
    perform ZERO window-kernel Python traces — every fleet shape binds
    from the AOT cache the first incarnation exported. Writes the
    daemon's schema-v7 serve.* metrics document as the stage artifact.
    CPU-deterministic (the kill is wall-clock-timed but the chains are
    virtual-time functions, so WHERE it lands never changes the bar)."""
    import tempfile

    from shadow_tpu.fleet import build_fleet, load_sweep
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.serve.client import ServeClient, ServeClientError

    doc = {
        **_fleet_smoke_job(seed=1, stop_s=1.0, num_hosts=num_hosts,
                           msgload=msgload),
        **{k: json.loads(json.dumps(v))
           for k, v in _SERVE_SMOKE_SWEEP.items()},
    }

    with tempfile.TemporaryDirectory(prefix="serve_smoke_") as td:
        state_dir = os.path.join(td, "state")
        cache_dir = os.path.join(td, "cache")  # fresh: cold → warm is real
        sock = os.path.join(state_dir, "serve.sock")
        env = _cpu_child_env(cache_dir)

        def start():
            proc = subprocess.Popen(
                [sys.executable, "-m", "shadow_tpu", "serve",
                 "--state-dir", state_dir,
                 "--checkpoint-every-dispatches", "1"],
                env=env, cwd=_REPO,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )
            client = ServeClient(sock, timeout=30)
            client.wait_ready(timeout_s=120)
            return proc, client

        t0 = time.perf_counter()
        proc, client = start()
        sid = client.submit(doc)["id"]
        killed_at = None
        while True:
            info = client.sweep(sid)
            progress = info.get("progress") or {}
            if info["status"] in ("done", "failed"):
                break  # too fast to kill mid-run; gate still meaningful
            if progress.get("jobs_done", 0) >= 2:
                killed_at = dict(progress)
                break
            time.sleep(0.1)
        proc.kill()
        proc.wait()

        proc, client = start()
        info = client.wait(sid, timeout_s=600)
        stats = info["stats"] or {}
        metrics_doc = client.metrics()
        try:
            client.drain()
        except ServeClientError:
            pass
        proc.wait(timeout=60)
        wall = time.perf_counter() - t0

    metrics_path = os.path.join(_REPO, "serve_smoke.metrics.json")
    with open(metrics_path, "w") as f:
        json.dump(metrics_doc, f, indent=1)
        f.write("\n")
    obs_metrics.validate_metrics_doc(metrics_doc)

    # uninterrupted reference: the same sweep as one in-process fleet
    jobs, _ = load_sweep(json.loads(json.dumps(doc)))
    ref = build_fleet(jobs, lanes=2, windows_per_dispatch=2)
    ref.run()
    ref_rows = ref.results()
    rows = info.get("results") or []
    chains_equal = (
        [r["name"] for r in rows] == [r["name"] for r in ref_rows]
        and [r.get("audit", {}).get("chain") for r in rows]
        == [r["audit"]["chain"] for r in ref_rows]
    )
    zero_recompiles = stats.get("kernel_traces", -1) == 0
    serve_counters = {
        k: v for k, v in metrics_doc["counters"].items()
        if k.startswith("serve.")
    }
    return {
        "stage": "serve_smoke",
        "hosts": num_hosts,
        "jobs": len(ref_rows),
        "killed_at": killed_at,
        "status": info["status"],
        "wall_s": round(wall, 3),
        "chains_equal": chains_equal,
        "restart_kernel_traces": stats.get("kernel_traces"),
        "serve": serve_counters,
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_chains": bool(chains_equal and info["status"] == "done"),
        "gate_zero_recompiles": bool(zero_recompiles),
        "gate": bool(
            chains_equal and info["status"] == "done" and zero_recompiles
            and killed_at is not None
        ),
    }


def stage_federation_smoke(num_hosts: int = 64, msgload: int = 2):
    """Federated serve plane gate (ISSUE 18 acceptance): 3 daemons +
    the router, all sharing one kcache root. Choreography:

      1. warm one sweep through the router (pays the only traces);
      2. submit a mixed-tenant batch with a same-tenant burst — sticky
         affinity piles it onto one peer, so idle peers STEAL through
         the journaled handoff path (`federation.steals >= 1`);
      3. SIGKILL the loaded peer mid-sweep — the router's probe ladder
         declares it lost, replays its journal, and re-places every
         unfinished sweep onto the survivors.

    Gates: every batch sweep settles `done` with per-job audit chains
    bit-identical to an uninterrupted in-process fleet run of the same
    document; at least one steal and one failover-replayed sweep; ZERO
    window-kernel traces on every batch sweep (the shared AOT cache
    means peers that never saw the shape bind warm); and the router's
    schema-v16 `federation.*` metrics document STRICT-validates as the
    stage artifact. CPU-deterministic: the kill is wall-clock-timed but
    chains are virtual-time functions, so where it lands never changes
    the bar."""
    import tempfile

    from shadow_tpu.fleet import build_fleet, load_sweep
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.serve.client import ServeClient, ServeClientError

    def sweep_doc(name: str, seed: int) -> dict:
        return {
            **_fleet_smoke_job(seed=seed, stop_s=1.0, num_hosts=num_hosts,
                               msgload=msgload),
            "sweep": {
                "name": name,
                "lanes": 2,
                "matrix": {"general.seed": [seed, seed + 1]},
            },
            "fleet": {"windows_per_dispatch": 2},
        }

    batch = [
        # the same-tenant burst (affinity pile-up -> steal pressure) ...
        ("team-a", sweep_doc("fed-a0", 21)),
        ("team-a", sweep_doc("fed-a1", 31)),
        ("team-a", sweep_doc("fed-a2", 41)),
        ("team-a", sweep_doc("fed-a3", 51)),
        # ... plus a second tenant so placement is mixed, not monoculture
        ("team-b", sweep_doc("fed-b0", 61)),
        ("team-b", sweep_doc("fed-b1", 71)),
    ]

    with tempfile.TemporaryDirectory(prefix="federation_smoke_") as td:
        cache_dir = os.path.join(td, "cache")  # ONE root, all peers
        env = _cpu_child_env(cache_dir)
        peers = {f"p{i}": os.path.join(td, f"p{i}") for i in range(3)}
        router_dir = os.path.join(td, "router")

        def start_peer(name: str):
            return subprocess.Popen(
                [sys.executable, "-m", "shadow_tpu", "serve",
                 "--state-dir", peers[name],
                 "--checkpoint-every-dispatches", "1"],
                env=env, cwd=_REPO,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )

        t0 = time.perf_counter()
        procs = {name: start_peer(name) for name in peers}
        for name in peers:
            ServeClient(
                os.path.join(peers[name], "serve.sock"), timeout=30
            ).wait_ready(timeout_s=120)
        router_proc = subprocess.Popen(
            [sys.executable, "-m", "shadow_tpu", "route",
             "--state-dir", router_dir,
             "--probe-interval", "0.25", "--lost-after", "3",
             "--peers"] + [f"{n}={d}" for n, d in peers.items()],
            env=env, cwd=_REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        router = ServeClient(
            os.path.join(router_dir, "route.sock"), timeout=30, retries=3
        )
        router.wait_ready(timeout_s=120)

        # 1. warm the shared kcache through the router (the ONLY traces)
        warm = router.submit(sweep_doc("fed-warm", 11), tenant="warm")
        router.wait(warm["id"], timeout_s=600)

        # 2. the batch: a burst faster than the probe refresh, so sticky
        # affinity piles team-a onto one peer and the stealer has work
        placed = [
            (router.submit(doc, tenant=tenant), tenant)
            for tenant, doc in batch
        ]
        handles = [out["id"] for out, _ in placed]
        pile_peer = placed[0][0]["peer"]

        # 3. wait for a steal, then SIGKILL the loaded peer mid-sweep
        steals = 0
        deadline = time.time() + 120
        while time.time() < deadline:
            steals = router.metrics()["counters"].get(
                "federation.steals", 0
            )
            if steals >= 1:
                break
            time.sleep(0.2)
        procs[pile_peer].kill()
        procs[pile_peer].wait()

        results: dict[str, dict] = {}
        for h in handles:
            results[h] = router.wait(h, timeout_s=900)
        metrics_doc = router.metrics()
        health = router.health()
        try:
            router.drain()
        except ServeClientError:
            pass
        router_proc.wait(timeout=60)
        for name, proc in procs.items():
            if name == pile_peer:
                continue
            try:
                ServeClient(
                    os.path.join(peers[name], "serve.sock"), timeout=30
                ).drain()
            except ServeClientError:
                pass
            proc.wait(timeout=60)
        wall = time.perf_counter() - t0

    metrics_path = os.path.join(_REPO, "federation_smoke.metrics.json")
    with open(metrics_path, "w") as f:
        json.dump(metrics_doc, f, indent=1)
        f.write("\n")
    # STRICT validation: federation.* must be a registered namespace
    obs_metrics.validate_metrics_doc(metrics_doc, strict_namespaces=True)

    # uninterrupted references: each doc as one in-process fleet
    chains_equal = True
    zero_recompiles = True
    for (tenant, doc), h in zip(batch, handles):
        info = results[h]
        jobs, _ = load_sweep(json.loads(json.dumps(doc)))
        ref = build_fleet(jobs, lanes=2, windows_per_dispatch=2)
        ref.run()
        ref_rows = ref.results()
        rows = info.get("results") or []
        if not (
            info["status"] == "done"
            and [r["name"] for r in rows] == [r["name"] for r in ref_rows]
            and [r.get("audit", {}).get("chain") for r in rows]
            == [r["audit"]["chain"] for r in ref_rows]
        ):
            chains_equal = False
        if (info.get("stats") or {}).get("kernel_traces", -1) != 0:
            zero_recompiles = False

    counters = metrics_doc["counters"]
    gate_steals = counters.get("federation.steals", 0) >= 1
    gate_failover = (
        counters.get("federation.failovers", 0) >= 1
        and counters.get("federation.replayed_sweeps", 0) >= 1
    )
    return {
        "stage": "federation_smoke",
        "hosts": num_hosts,
        "peers": len(peers),
        "sweeps": len(batch),
        "pile_peer": pile_peer,
        "wall_s": round(wall, 3),
        "statuses": {h: results[h]["status"] for h in handles},
        "chains_equal": chains_equal,
        "federation": {
            k: v for k, v in counters.items()
            if k.startswith("federation.")
        },
        "peers_up": health.get("peers_up"),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_chains": bool(chains_equal),
        "gate_zero_recompiles": bool(zero_recompiles),
        "gate_steals": bool(gate_steals),
        "gate_failover": bool(gate_failover),
        "gate": bool(
            chains_equal and zero_recompiles and gate_steals
            and gate_failover
        ),
    }


def stage_pipeline_smoke(hosts: int = 256, msgload: int = 2,
                         stop_s: int = 12, wpd: int = 4,
                         drain_ms: float = 40.0):
    """Pipelined CPU↔TPU handoff gate (ISSUE 15 acceptance).

    Four chain-equality arms prove the two-slot pipeline changes WHEN
    dispatches are enqueued, never what they compute: {conservative,
    optimistic, async-islands, fleet} each run pipelined AND serial
    (`experimental.pipelined_dispatch: false`), audit chains + committed
    events bit-identical per pair.

    The wall-clock arm runs a HANDOFF-HEAVY conservative workload: short
    fused dispatches (small windows_per_dispatch) with a per-handoff
    host-drain model attached through `Simulation.add_handoff_hook` — a
    blocking wait of `drain_ms` standing in for the managed-plane
    syscall drain (procs/bridge.py waits on child-process IPC between
    windows; the pure-device bench has no children, so the wait is
    modeled, and it is WAIT, not host compute — exactly the latency
    class the pipeline hides). Serial pays device + drain per boundary;
    pipelined pays max(device, drain) — the gate demands >= 1.2x.

    Also gated: the schema-v14 metrics artifact (pipeline.* recorded,
    strict-validated), zero kernel retraces on the pipelined arm with
    the same compile count as the serial arm (pipelining must not add a
    compile), and trace-derived overlap efficiency > 0 (the
    issue/await/host_drain spans tools/trace_summary.py reads).

    CPU-deterministic (both arms share one backend), so no backend
    wait."""
    import importlib.util
    import jax

    from shadow_tpu.core import simtime
    from shadow_tpu.analysis import hlo_audit
    from shadow_tpu.flagship import build_phold_flagship
    from shadow_tpu.fleet import JobSpec, build_fleet
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.obs.trace import ChromeTracer
    from shadow_tpu.sim import build_simulation

    _enable_compile_cache()

    # ---- chain-equality arms (small, shared shapes) ----
    gml = _async_smoke_gml(2, 4)

    def small_cfg(pipelined, **exp):
        hosts_d = {}
        for v in range(8):
            hosts_d[f"h{v:02d}"] = {
                "quantity": 1, "network_node_id": v, "app_model": "phold",
                "app_options": {"msgload": 1, "runtime": 6,
                                "local_span": 2},
            }
        experimental = {
            "event_capacity": 1024, "events_per_host_per_window": 8,
            "outbox_slots": 8, "inbox_slots": 4,
            "pipelined_dispatch": pipelined,
        }
        experimental.update(exp)
        return {
            "general": {"stop_time": 8, "seed": 42},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "experimental": experimental,
            "hosts": hosts_d,
        }

    def chain_of(sim):
        return int(sim.audit_chain()), int(
            sim.counters()["events_committed"]
        )

    arms = {}

    def pair(name, runner, mk):
        piped, serial = mk(True), mk(False)
        runner(piped)
        runner(serial)
        cp, cs = chain_of(piped), chain_of(serial)
        arms[name] = {
            "chain": cp[0], "events": cp[1], "equal": cp == cs,
        }
        return piped

    pair("conservative", lambda s: s.run(windows_per_dispatch=8),
         lambda p: build_simulation(small_cfg(p)))
    pair("optimistic", lambda s: s.run_optimistic(),
         lambda p: build_simulation(small_cfg(p)))
    pair("async_islands", lambda s: s.run(windows_per_dispatch=8),
         lambda p: build_simulation(
             small_cfg(p, num_shards=2, exchange_slots=16)))

    def mk_fleet(pipelined):
        jobs = [
            JobSpec(f"j{i}", small_cfg(pipelined))
            for i in range(3)
        ]
        for i, j in enumerate(jobs):
            j.config["general"]["seed"] = 42 + i  # data-plane sweep axis
        return build_fleet(jobs, lanes=2)

    piped_fleet, serial_fleet = mk_fleet(True), mk_fleet(False)
    piped_fleet.run()
    serial_fleet.run()
    rows_p = {r["name"]: r["audit"]["chain"] for r in piped_fleet.results()}
    rows_s = {r["name"]: r["audit"]["chain"] for r in serial_fleet.results()}
    arms["fleet"] = {
        "chain": rows_p.get("j0", 0),
        "events": sum(
            r["events_committed"] for r in piped_fleet.results()
        ),
        "equal": rows_p == rows_s and bool(rows_p),
    }
    gate_chain = all(a["equal"] for a in arms.values())

    # ---- wall-clock arm: handoff-heavy workload + drain model ----
    drain_s = drain_ms / 1e3

    def drain_model(sim, mn):
        # the managed-plane syscall-drain stand-in: a blocking WAIT at
        # every handoff boundary (state untouched — quiet by contract)
        time.sleep(drain_s)

    def timing_arm(pipelined, tracer=None):
        sim = build_phold_flagship(
            hosts, msgload=msgload, stop_s=stop_s, runtime_s=stop_s - 1,
            seed=7, pipelined_dispatch=pipelined,
        )
        sim.obs_session = obs_metrics.ObsSession(tracer=tracer)
        # warm the compile, then time the steady region with the drain
        sim.run(until=2 * simtime.NS_PER_SEC, windows_per_dispatch=wpd)
        sim.add_handoff_hook(drain_model)
        t0 = time.perf_counter()
        sim.run(windows_per_dispatch=wpd)
        wall = time.perf_counter() - t0
        return sim, wall

    # interleave arms to decorrelate machine drift from the comparison
    serial_sim, w_s = timing_arm(False)
    tracer = ChromeTracer()
    piped_sim, w_p = timing_arm(True, tracer=tracer)
    w_s = min(w_s, timing_arm(False)[1])
    w_p = min(w_p, timing_arm(True)[1])
    timing_equal = chain_of(piped_sim) == chain_of(serial_sim)
    gate_wall = w_p > 0 and (w_s / w_p) >= 1.2

    # retrace-free: pipelining must not add a compile — one lowering per
    # bound kernel, and the same compile count as the serial arm
    retrace_p = hlo_audit.retrace_report(piped_sim)
    retrace_s = hlo_audit.retrace_report(serial_sim)
    gate_retrace = bool(
        retrace_p["ok"]
        and retrace_p["compiles_total"] == retrace_s["compiles_total"]
    )

    # trace-derived overlap efficiency (tools/trace_summary.py)
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(_REPO, "tools", "trace_summary.py")
    )
    trace_summary = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_summary)
    overlap = trace_summary.overlap_stats(tracer.to_doc()) or {}

    # schema-v14 artifact from the pipelined timing arm
    metrics_path = os.path.join(_REPO, "pipeline_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(piped_sim)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "pipeline_smoke", "hosts": hosts,
        "drain_model_ms": drain_ms,
    })
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    pstats = piped_sim.pipeline_stats()
    gate_schema = bool(
        doc["counters"].get("pipeline.issued_ahead", 0) > 0
        and doc["counters"].get("pipeline.overlap_ns", 0) > 0
    )

    return {
        "stage": "pipeline_smoke",
        "platform": jax.default_backend(),
        "hosts": hosts,
        "windows_per_dispatch": wpd,
        "host_drain_model_ms": drain_ms,
        "arms": arms,
        "timing_chain_equal": bool(timing_equal),
        "wall_serial_s": round(w_s, 3),
        "wall_pipelined_s": round(w_p, 3),
        "wall_ratio": round(w_s / w_p, 2) if w_p else 0.0,
        "pipeline": {k: int(v) for k, v in sorted(pstats.items())},
        "overlap_efficiency": round(
            float(overlap.get("overlap_efficiency", 0.0)), 3
        ),
        "kernel_compiles": int(retrace_p["compiles_total"]),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_chain": bool(gate_chain and timing_equal),
        "gate_wall": bool(gate_wall),
        "gate_retrace": gate_retrace,
        "gate_schema": gate_schema,
        "gate": bool(
            gate_chain and timing_equal and gate_wall and gate_retrace
            and gate_schema
        ),
    }


def stage_hostplane_smoke(hosts: int = 48, msgload: int = 2,
                          stop_s: int = 12, wpd: int = 4,
                          per_host_drain_ms: float = 1.0):
    """Multi-worker host-plane gate (ISSUE 17 acceptance).

    Five chain-equality arms prove the host plane changes WHO executes
    partition-local handoff work, never what it computes or the order it
    commits: {conservative, optimistic, async-islands, fleet,
    pipelined-conservative} each run with `experimental.host_workers: 4`
    AND the serial path (`host_workers: 1`), audit chains + committed
    events bit-identical per pair — and every pair registers a sharded
    recorder hook whose (frontier, gid) coverage must match exactly,
    proving the fan-out visits the same partitions either way.

    The wall-clock arm runs a HANDOFF-HEAVY conservative workload: a
    per-host drain model attached through
    `Simulation.add_handoff_hook(fn, sharded=True)` — a blocking wait of
    `per_host_drain_ms` PER HOST standing in for partition-local
    syscall/IPC servicing (the latency class PARSIR binds to per-worker
    queues). The serial arm pays hosts x wait per boundary; the 4-worker
    arm pays ~hosts/4 x wait — the gate demands >= 1.2x overall wall.

    Also gated: the schema-v15 metrics artifact (hostplane.* recorded
    with sharded_drains > 0 and ZERO serial_fallbacks,
    strict-validated), zero kernel retraces with the SAME compile count
    as the serial arm (the host plane never touches the device program),
    and trace-derived drain parallelism > 1 from the per-worker
    host_drain spans tools/trace_summary.py reads.

    CPU-deterministic (all arms share one backend), so no backend
    wait."""
    import importlib.util
    import jax

    from shadow_tpu.core import simtime
    from shadow_tpu.analysis import hlo_audit
    from shadow_tpu.flagship import build_phold_flagship
    from shadow_tpu.fleet import JobSpec, build_fleet
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.obs.trace import ChromeTracer
    from shadow_tpu.sim import build_simulation

    _enable_compile_cache()

    # ---- chain-equality arms (small, shared shapes) ----
    gml = _async_smoke_gml(2, 4)

    def small_cfg(workers, **exp):
        hosts_d = {}
        for v in range(8):
            hosts_d[f"h{v:02d}"] = {
                "quantity": 1, "network_node_id": v, "app_model": "phold",
                "app_options": {"msgload": 1, "runtime": 6,
                                "local_span": 2},
            }
        experimental = {
            "event_capacity": 1024, "events_per_host_per_window": 8,
            "outbox_slots": 8, "inbox_slots": 4,
            "host_workers": workers,
        }
        experimental.update(exp)
        return {
            "general": {"stop_time": 8, "seed": 42},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "experimental": experimental,
            "hosts": hosts_d,
        }

    def chain_of(sim):
        return int(sim.audit_chain()), int(
            sim.counters()["events_committed"]
        )

    arms = {}

    def pair(name, runner, mk):
        multi, serial = mk(4), mk(1)
        hits_m, hits_s = [], []
        multi.add_handoff_hook(
            lambda s, mn, gid, h=hits_m: h.append((int(mn), int(gid))),
            sharded=True,
        )
        serial.add_handoff_hook(
            lambda s, mn, gid, h=hits_s: h.append((int(mn), int(gid))),
            sharded=True,
        )
        runner(multi)
        runner(serial)
        cm, cs = chain_of(multi), chain_of(serial)
        hp = multi.hostplane_stats()
        arms[name] = {
            "chain": cm[0], "events": cm[1],
            "equal": bool(
                cm == cs
                and sorted(hits_m) == sorted(hits_s)
                and bool(hits_m)
                and hp.get("sharded_drains", 0) > 0
                and serial.hostplane_stats() == {}
            ),
        }
        return multi

    pair("conservative", lambda s: s.run(windows_per_dispatch=8),
         lambda w: build_simulation(small_cfg(w)))
    pair("optimistic", lambda s: s.run_optimistic(),
         lambda w: build_simulation(small_cfg(w)))
    pair("async_islands", lambda s: s.run(windows_per_dispatch=8),
         lambda w: build_simulation(
             small_cfg(w, num_shards=2, exchange_slots=16)))
    pair("conservative_pipelined",
         lambda s: s.run(windows_per_dispatch=8),
         lambda w: build_simulation(small_cfg(w, pipelined_dispatch=True)))

    def mk_fleet(workers):
        jobs = [
            JobSpec(f"j{i}", small_cfg(workers))
            for i in range(3)
        ]
        for i, j in enumerate(jobs):
            j.config["general"]["seed"] = 42 + i  # data-plane sweep axis
        return build_fleet(jobs, lanes=2)

    multi_fleet, serial_fleet = mk_fleet(4), mk_fleet(1)
    lane_hits_m, lane_hits_s = [], []
    multi_fleet.add_handoff_hook(
        lambda f, mn, lane, h=lane_hits_m: h.append(int(lane)),
        sharded=True,
    )
    serial_fleet.add_handoff_hook(
        lambda f, mn, lane, h=lane_hits_s: h.append(int(lane)),
        sharded=True,
    )
    multi_fleet.run()
    serial_fleet.run()
    rows_m = {r["name"]: r["audit"]["chain"] for r in multi_fleet.results()}
    rows_s = {r["name"]: r["audit"]["chain"] for r in serial_fleet.results()}
    arms["fleet"] = {
        "chain": rows_m.get("j0", 0),
        "events": sum(
            r["events_committed"] for r in multi_fleet.results()
        ),
        "equal": bool(
            rows_m == rows_s and bool(rows_m)
            and sorted(lane_hits_m) == sorted(lane_hits_s)
            and multi_fleet.hostplane_stats().get("sharded_drains", 0) > 0
        ),
    }
    gate_chain = all(a["equal"] for a in arms.values())

    # ---- wall-clock arm: handoff-heavy workload + per-host drain ----
    drain_s = per_host_drain_ms / 1e3

    def drain_model(sim, mn, gid):
        # the partition-local syscall-drain stand-in: a blocking WAIT
        # per host at every handoff boundary (state untouched — quiet
        # and partition-local by contract, so the plane may shard it)
        time.sleep(drain_s)

    def timing_arm(workers, tracer=None):
        sim = build_phold_flagship(
            hosts, msgload=msgload, stop_s=stop_s, runtime_s=stop_s - 1,
            seed=7, host_workers=workers,
        )
        sim.obs_session = obs_metrics.ObsSession(tracer=tracer)
        # warm the compile, then time the steady region with the drain
        sim.run(until=2 * simtime.NS_PER_SEC, windows_per_dispatch=wpd)
        sim.add_handoff_hook(drain_model, sharded=True)
        t0 = time.perf_counter()
        sim.run(windows_per_dispatch=wpd)
        wall = time.perf_counter() - t0
        return sim, wall

    # interleave arms to decorrelate machine drift from the comparison
    serial_sim, w_s = timing_arm(1)
    tracer = ChromeTracer()
    multi_sim, w_m = timing_arm(4, tracer=tracer)
    w_s = min(w_s, timing_arm(1)[1])
    w_m = min(w_m, timing_arm(4)[1])
    timing_equal = chain_of(multi_sim) == chain_of(serial_sim)
    gate_wall = w_m > 0 and (w_s / w_m) >= 1.2

    # retrace-free: the host plane must not add a compile — one lowering
    # per bound kernel, and the same compile count as the serial arm
    retrace_m = hlo_audit.retrace_report(multi_sim)
    retrace_s = hlo_audit.retrace_report(serial_sim)
    gate_retrace = bool(
        retrace_m["ok"]
        and retrace_m["compiles_total"] == retrace_s["compiles_total"]
    )

    # trace-derived drain parallelism (tools/trace_summary.py)
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(_REPO, "tools", "trace_summary.py")
    )
    trace_summary = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_summary)
    drain = trace_summary.drain_parallelism(tracer.to_doc()) or {}

    # schema-v15 artifact from the 4-worker timing arm
    metrics_path = os.path.join(_REPO, "hostplane_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(multi_sim)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "hostplane_smoke", "hosts": hosts,
        "per_host_drain_ms": per_host_drain_ms,
    })
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    hpstats = multi_sim.hostplane_stats()
    gate_schema = bool(
        doc["counters"].get("hostplane.workers", 0) == 4
        and doc["counters"].get("hostplane.sharded_drains", 0) > 0
        and doc["counters"].get("hostplane.serial_fallbacks", -1) == 0
    )

    return {
        "stage": "hostplane_smoke",
        "platform": jax.default_backend(),
        "hosts": hosts,
        "windows_per_dispatch": wpd,
        "per_host_drain_ms": per_host_drain_ms,
        "arms": arms,
        "timing_chain_equal": bool(timing_equal),
        "wall_serial_s": round(w_s, 3),
        "wall_multi_s": round(w_m, 3),
        "wall_ratio": round(w_s / w_m, 2) if w_m else 0.0,
        "hostplane": {k: int(v) for k, v in sorted(hpstats.items())},
        "drain_parallelism": round(
            float(drain.get("parallelism", 0.0)), 2
        ),
        "kernel_compiles": int(retrace_m["compiles_total"]),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_chain": bool(gate_chain and timing_equal),
        "gate_wall": bool(gate_wall),
        "gate_parallel": bool(drain.get("parallelism", 0.0) > 1.0),
        "gate_retrace": gate_retrace,
        "gate_schema": gate_schema,
        "gate": bool(
            gate_chain and timing_equal and gate_wall
            and drain.get("parallelism", 0.0) > 1.0 and gate_retrace
            and gate_schema
        ),
    }


def stage_qdisc_smoke(stop_s: int = 3, wpd: int = 8):
    """Per-interface scheduling-plane gate (ISSUE 19 acceptance).

    Arms, all CPU-deterministic:

    - default-compat: the SAME overloaded flood run three ways — no
      qdisc section, explicit `qdisc: {discipline: fifo}`, and the
      legacy `experimental.interface_qdisc: fifo` string — must produce
      bit-identical audit chains (the discipline-interface reroute of
      nic.py's send ring is invisible to default runs).
    - eiffel-vs-exact: the bucketed discipline in its exactness regime
      (fifo rank → rank spread 0 < B) against exact PIFO: chains AND the
      full qdisc.* counter plane (enqueues/drops/sojourn) bit-identical.
    - driver matrix: one pifo+wfq+codel config chain-identical under
      {conservative, optimistic, async-islands(2), fleet} — the queue
      plane is ordinary [H]-leading sub-state, so every execution engine
      composes.
    - separation: a bandwidth-starved udp_echo bufferbloat workload
      (64-deep drop-tail ring vs pifo with the CoDel drop hook): the
      FIFO arm's mean RTT must exceed the CoDel arm's by >= 1.5x —
      the scheduling plane visibly changes end-to-end behavior, not
      just counters.
    - retrace-free + schema: zero kernel retraces on the pifo arm, and
      its metrics artifact strict-validates at schema v17 with live
      qdisc.* counters."""
    import jax
    import numpy as np

    from shadow_tpu.analysis import hlo_audit
    from shadow_tpu.fleet import JobSpec, build_fleet
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.sim import build_simulation

    _enable_compile_cache()

    # 400B datagram = 428B wire = ~34 ms at 100 Kbit, sent every 5 ms:
    # the send queue must absorb a 7x overload
    gml_slow = (
        'graph [ node [ id 0 bandwidth_down "10 Mbit" '
        'bandwidth_up "100 Kbit" ] '
        'edge [ source 0 target 0 latency "10 ms" packet_loss 0.0 ] ]'
    )

    def flood_cfg(qdisc=None, seed=6, **exp):
        experimental = {
            "event_capacity": 4096, "events_per_host_per_window": 8,
        }
        experimental.update(exp)
        cfg = {
            "general": {"stop_time": stop_s, "seed": seed},
            "network": {"graph": {"type": "gml", "inline": gml_slow}},
            "experimental": experimental,
            "hosts": {
                "server": {"app_model": "udp_flood",
                           "app_options": {"role": "server"},
                           "bandwidth_down": "10 Mbit",
                           "bandwidth_up": "10 Mbit"},
                "client": {"quantity": 3, "app_model": "udp_flood",
                           "app_options": {"interval": "5 ms",
                                           "size": 400,
                                           "runtime": stop_s - 1}},
            },
        }
        if qdisc:
            cfg["qdisc"] = qdisc
        return cfg

    def chain_of(sim):
        return int(sim.audit_chain()), int(
            sim.counters()["events_committed"]
        )

    def run(cfg, runner=None):
        sim = build_simulation(cfg)
        if runner is None:
            sim.run(windows_per_dispatch=wpd)
        else:
            runner(sim)
        return sim

    # ---- default-compat arm ----
    c_none = chain_of(run(flood_cfg()))
    c_section = chain_of(run(flood_cfg(qdisc={"discipline": "fifo"})))
    c_legacy = chain_of(run(flood_cfg(interface_qdisc="fifo")))
    gate_default = c_none == c_section == c_legacy

    # ---- eiffel-vs-exact parity arm (rank spread 0 < B = 8) ----
    pifo_sim = run(flood_cfg(qdisc={"discipline": "pifo",
                                    "queue_slots": 32}))
    eiffel_sim = run(flood_cfg(qdisc={"discipline": "eiffel",
                                      "queue_slots": 32, "buckets": 8}))
    qp = jax.device_get(pifo_sim.state.subs["qdisc"])
    qe = jax.device_get(eiffel_sim.state.subs["qdisc"])
    counter_keys = ("enqueues", "dequeues", "drops_overflow", "drops_red",
                    "drops_codel", "sojourn_sum", "depth_peak")
    counters_equal = all(
        bool((np.asarray(qp[k]) == np.asarray(qe[k])).all())
        for k in counter_keys
    )
    gate_eiffel = bool(
        chain_of(pifo_sim) == chain_of(eiffel_sim) and counters_equal
    )

    # ---- driver matrix arm ----
    qfull = {"discipline": "pifo", "rank": "wfq", "drop": "codel",
             "queue_slots": 32}
    cons_sim = run(flood_cfg(qdisc=qfull))
    c_cons = chain_of(cons_sim)
    c_opt = chain_of(run(flood_cfg(qdisc=qfull),
                         runner=lambda s: s.run_optimistic()))
    c_isl = chain_of(run(flood_cfg(qdisc=qfull, num_shards=2,
                                   exchange_slots=16)))
    jobs = [JobSpec(f"j{i}", flood_cfg(qdisc=qfull, seed=6 + i))
            for i in range(2)]
    fl = build_fleet(jobs, lanes=2)
    fl.run()
    rows = {r["name"]: (r["audit"]["chain"], r["events_committed"])
            for r in fl.results()}
    gate_drivers = bool(
        c_cons == c_opt == c_isl and rows.get("j0") == c_cons
    )

    # ---- separation arm: bufferbloat RTT, drop-tail vs CoDel ----
    gml_echo = (
        'graph [ '
        'node [ id 0 bandwidth_down "10 Mbit" bandwidth_up "10 Mbit" ] '
        'node [ id 1 bandwidth_down "10 Mbit" bandwidth_up "500 Kbit" ] '
        'edge [ source 0 target 0 latency "1 ms" packet_loss 0.0 ] '
        'edge [ source 1 target 1 latency "1 ms" packet_loss 0.0 ] '
        'edge [ source 0 target 1 latency "5 ms" packet_loss 0.0 ] ]'
    )

    def echo_cfg(qdisc=None):
        cfg = {
            "general": {"stop_time": 8, "seed": 5},
            "network": {"graph": {"type": "gml", "inline": gml_echo}},
            "experimental": {"event_capacity": 4096,
                             "events_per_host_per_window": 8},
            "hosts": {
                "server": {"network_node_id": 0, "app_model": "udp_echo",
                           "app_options": {"role": "server"}},
                "client": {"network_node_id": 1, "app_model": "udp_echo",
                           "app_options": {"interval": "2 ms",
                                           "size": 512, "runtime": 6}},
            },
        }
        if qdisc:
            cfg["qdisc"] = qdisc
        return cfg

    def rtt_mean_ms(sim):
        sub = jax.device_get(sim.state.subs["udp_echo"])
        n = int(np.sum(np.asarray(sub["rtt_count"])))
        return (
            float(np.sum(np.asarray(sub["rtt_sum"]))) / n / 1e6
            if n else 0.0
        )

    rtt_fifo = rtt_mean_ms(run(echo_cfg()))
    codel_sim = run(echo_cfg({"discipline": "pifo", "drop": "codel"}))
    rtt_codel = rtt_mean_ms(codel_sim)
    gate_separation = bool(
        rtt_codel > 0 and rtt_fifo >= 1.5 * rtt_codel
    )

    # ---- retrace + schema arms (on the full-feature pifo sim) ----
    retrace = hlo_audit.retrace_report(cons_sim)
    gate_retrace = bool(retrace["ok"])

    metrics_path = os.path.join(_REPO, "qdisc_smoke.metrics.json")
    session = obs_metrics.ObsSession()
    session.finalize(cons_sim)
    doc = session.metrics.dump(metrics_path, meta={
        "stage": "qdisc_smoke", "discipline": "pifo", "rank": "wfq",
        "drop": "codel",
    })
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    gate_schema = bool(
        doc["schema_version"] == obs_metrics.SCHEMA_VERSION
        and doc["counters"].get("qdisc.enqueues", 0) > 0
        and doc["counters"].get("qdisc.dequeues", 0) > 0
    )

    return {
        "stage": "qdisc_smoke",
        "platform": jax.default_backend(),
        "chain": c_cons[0],
        "events": c_cons[1],
        "rtt_fifo_ms": round(rtt_fifo, 2),
        "rtt_codel_ms": round(rtt_codel, 2),
        "rtt_ratio": round(rtt_fifo / rtt_codel, 2) if rtt_codel else 0.0,
        "qdisc": {k: int(np.sum(np.asarray(qp[k])))
                  for k in counter_keys},
        "kernel_compiles": int(retrace["compiles_total"]),
        "metrics_out": os.path.relpath(metrics_path, _REPO),
        "gate_default": bool(gate_default),
        "gate_eiffel": gate_eiffel,
        "gate_drivers": gate_drivers,
        "gate_separation": gate_separation,
        "gate_retrace": gate_retrace,
        "gate_schema": gate_schema,
        "gate": bool(
            gate_default and gate_eiffel and gate_drivers
            and gate_separation and gate_retrace and gate_schema
        ),
    }


def stage_lint_smoke():
    """shadowlint gate (ISSUE 7 acceptance, extended by ISSUE 14): all
    FOUR static-analysis passes over the tree must report ZERO
    non-baselined violations — the STL0xx AST rules, the SLC0xx
    cross-plane contract auditor, the STH0xx host-thread race lint, and
    the HLO budget ledger (every kernel variant this box can lower,
    against shadow_tpu/analysis/hlo_baseline.json) — and a tiny geared
    driver run must show no kernel retraces (one lowering per bound
    kernel — the compile-cache-miss perf-bug class from r03–r05).
    Pure CPU (AST walks + tiny compiles)."""
    from shadow_tpu.analysis import contracts, hlo_audit, linter, threads
    from shadow_tpu.flagship import build_phold_flagship

    paths = [os.path.join(_REPO, p) for p in ("shadow_tpu", "tools", "bench.py")]
    findings = linter.lint_paths(paths, _REPO)
    findings += contracts.audit_tree(_REPO)
    findings += threads.lint_threads_paths(_REPO)
    # the HLO budget ledger: a missing/corrupt baseline is a gate
    # failure with a remediation hint, not a traceback
    hlo_problems = []
    hlo_baseline_ok = True
    try:
        hlo_baseline = hlo_audit.load_hlo_baseline(
            hlo_audit.baseline_path(_REPO)
        )
    except hlo_audit.HloBaselineError as e:
        hlo_baseline_ok = False
        hlo_problems = [str(e)]
    if hlo_baseline_ok:
        ledger = hlo_audit.budget_ledger(
            hlo_audit.default_ledger_variants()
        )
        hlo_problems = hlo_audit.check_ledger(ledger, hlo_baseline)
    findings += [
        linter.Finding(
            path="shadow_tpu/analysis/hlo_baseline.json", line=1, col=0,
            code="SLH001", message=p, text=p.split(":", 1)[0],
        )
        for p in hlo_problems
    ]
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    baseline = linter.load_baseline(os.path.join(_REPO, linter.BASELINE_NAME))
    new, old = linter.split_baselined(findings, baseline)
    scanned = list(linter.iter_python_files(paths))
    pass_of = {"STL": "lint", "SLC": "contracts", "STH": "threads",
               "SLH": "hlo"}
    passes = {"lint": 0, "contracts": 0, "threads": 0, "hlo": 0}
    for f in new:
        passes[pass_of[f.code[:3]]] += 1
    doc = linter.findings_doc(new, old, scanned, passes=passes)
    report_path = os.path.join(_REPO, "lint_smoke.report.json")
    with open(report_path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")

    # retrace smoke: a geared conservative run + an optimistic run, every
    # bound kernel lowered at most once (hlo_audit retrace detector)
    sim = build_phold_flagship(
        64, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=4096,
        pool_gears=2)
    sim.run()
    retrace = hlo_audit.retrace_report(sim)
    return {
        "stage": "lint_smoke",
        "files_scanned": len(scanned),
        "findings_new": len(new),
        "findings_grandfathered": len(old),
        "by_code": doc["counts"]["by_code"],
        "passes": passes,
        "retrace_ok": bool(retrace["ok"]),
        "kernel_compiles": int(retrace["compiles_total"]),
        "report_out": os.path.relpath(report_path, _REPO),
        "gate_lint": passes["lint"] == 0,
        "gate_contracts": passes["contracts"] == 0,
        "gate_threads": passes["threads"] == 0,
        "gate_hlo_ledger": bool(hlo_baseline_ok and passes["hlo"] == 0),
        "gate_retrace": bool(retrace["ok"]),
        "gate": bool(not new and hlo_baseline_ok and retrace["ok"]),
    }


def main():
    _enable_compile_cache()
    if "--lint-smoke" in sys.argv:
        # static-analysis gate: all four shadowlint passes clean (AST
        # rules, contract auditor, thread race lint, HLO budget ledger)
        # + no kernel retraces. AST walks + tiny CPU compiles — no
        # accelerator.
        print(json.dumps(stage_lint_smoke()), flush=True)
        return
    if "--pipeline-smoke" in sys.argv:
        # pipelined-handoff gate: audit chains bit-identical pipelined
        # vs serial across {conservative, optimistic, async-islands,
        # fleet}, >= 1.2x wall on a handoff-heavy workload (the modeled
        # managed-plane drain hidden behind in-flight device work),
        # schema-v14 artifact, retrace-free. Both arms share one CPU
        # backend.
        print(json.dumps(stage_pipeline_smoke()), flush=True)
        return
    if "--hostplane-smoke" in sys.argv:
        # multi-worker host-plane gate: audit chains bit-identical
        # host_workers=4 vs 1 across {conservative, optimistic,
        # async-islands, fleet, pipelined}, >= 1.2x wall on a
        # handoff-heavy workload (the per-host drain model sharded
        # across pinned workers), schema-v15 artifact, drain
        # parallelism > 1, retrace-free. All arms share one CPU
        # backend.
        print(json.dumps(stage_hostplane_smoke()), flush=True)
        return
    if "--qdisc-smoke" in sys.argv:
        # per-interface scheduling gate: default-FIFO arm bit-identical
        # to pre-qdisc runs, eiffel-vs-exact chain parity, one pifo
        # config chain-identical across {conservative, optimistic,
        # islands, fleet}, drop-tail-vs-CoDel RTT separation on a
        # bufferbloat workload, retrace-free, schema-v17 artifact.
        # CPU-deterministic by design.
        print(json.dumps(stage_qdisc_smoke()), flush=True)
        return
    if "--serve-smoke" in sys.argv:
        # sim-as-a-service gate: submit → SIGKILL the daemon → restart →
        # journal replay finishes the sweep with bit-identical audit
        # chains and ZERO kernel retraces off the warm AOT cache. CPU-
        # deterministic by design.
        print(json.dumps(stage_serve_smoke()), flush=True)
        return
    if "--federation-smoke" in sys.argv:
        # federated serve gate: 3 peers + router sharing one kcache
        # root, mixed-tenant batch, steal under affinity pile-up,
        # SIGKILL one peer mid-sweep → journal-replay failover onto the
        # survivors with bit-identical chains and zero retraces.
        # CPU-deterministic by design.
        print(json.dumps(stage_federation_smoke()), flush=True)
        return
    if "--async-smoke" in sys.argv:
        # async conservative-sync gate: per-shard frontiers beat the
        # window barrier on an imbalanced islands workload with a
        # bit-identical audit chain. Both arms run the same backend, so
        # the comparison is CPU-deterministic.
        print(json.dumps(stage_async_smoke()), flush=True)
        return
    if "--profile-smoke" in sys.argv:
        # shadowscope gate: profiler-on vs off bit-identical chains at
        # <=3% overhead, critical-path attribution naming the skewed
        # shard, exact two-peer /timez histogram folds, and a strict-
        # validated schema-current artifact carrying prof.* keys. Both
        # arms share one CPU backend.
        print(json.dumps(stage_profile_smoke()), flush=True)
        return
    if "--mesh-smoke" in sys.argv:
        # true multi-chip gate: shard_map mesh execution with
        # neighbor-only ppermute frontier exchange + min-cut placement —
        # chains bit-identical to the single-program islands run, zero
        # all-gathers in the mesh kernel, collective volume scaling with
        # in-edge degree, retrace-free across a gear shift and a live
        # migration. Runs on 8 VIRTUAL CPU devices (the force must land
        # before the jax backend initializes).
        from shadow_tpu.parallel.virtualize import force_cpu_devices

        force_cpu_devices(8)
        print(json.dumps(stage_mesh_smoke()), flush=True)
        return
    if "--mesh-resilience-smoke" in sys.argv:
        # elastic-resilience gate: kill_chip mid-run → drain → relayout
        # onto the surviving mesh → re-expand on recovery, chains
        # bit-identical to the uninterrupted run (and to the wait-policy
        # control arm); the shrink-to-1 arm resumes on the global
        # engine. Runs on 8 VIRTUAL CPU devices (the force must land
        # before the jax backend initializes).
        from shadow_tpu.parallel.virtualize import force_cpu_devices

        force_cpu_devices(8)
        print(json.dumps(stage_mesh_resilience_smoke()), flush=True)
        return
    if "--balance-smoke" in sys.argv:
        # self-balancing gate: a skew_hosts-driven hot shard is detected
        # and healed by a verified live migration — lower frontier
        # spread + fewer blocked supersteps than the balancer-off arm,
        # bit-identical chains (incl. a forced mid-migration rollback),
        # zero retraces. All arms share one CPU backend.
        print(json.dumps(stage_balance_smoke()), flush=True)
        return
    if "--pressure-smoke" in sys.argv:
        # pressure-plane gate: exhaust_backend / saturate_pool injections
        # engage the degradation ladder and the run completes with the
        # uninterrupted chain. CPU-deterministic (the injection IS the
        # pressure).
        print(json.dumps(stage_pressure_smoke()), flush=True)
        return
    if "--resilience-smoke" in sys.argv:
        # backend-survivability gate: deterministic kill_backend → drain /
        # resume / CPU failover with bit-identical audit chains. CPU-
        # deterministic (the injection is the outage).
        print(json.dumps(stage_resilience_smoke()), flush=True)
        return
    if "--fault-smoke" in sys.argv:
        # fault-tolerance gate: quarantine-mode run with one injected
        # process kill completes rc=0 and records faults.* metrics.
        # Managed plane only — no accelerator.
        print(json.dumps(stage_fault_smoke()), flush=True)
        return
    if "--fleet-smoke" in sys.argv:
        # fleet gate: 8 mixed-length jobs as ONE device program — one
        # window-kernel compile, fleet wall < summed solo wall. A CPU
        # gate by design (compile amortization is the point).
        print(json.dumps(stage_fleet_smoke()), flush=True)
        return
    _run_stages(_require_tpu())


def _run_stages(dev):
    if "--stages" in sys.argv:
        # staged measurement configs (BASELINE.md 2-3); one JSON line each
        print(json.dumps(stage_udp_flood()))
        print(json.dumps(stage_tcp_bulk()))
        return
    if "--stages-100k" in sys.argv:
        # BASELINE configs 4-5 SHAPE at one-chip scale (VERDICT r3 #3)
        print(json.dumps(stage_phold_100k()))
        print(json.dumps(stage_udp_flood_100k()))
        return
    if "--shard-sweep" in sys.argv:
        shard_sweep(out_path=os.path.join(_REPO, "docs", "shard_sweep.json"))
        return
    if "--obs-smoke" in sys.argv:
        # telemetry-plane overhead gate (<= 3% step time with counters on)
        print(json.dumps(stage_obs_overhead()), flush=True)
        return
    if "--audit-smoke" in sys.argv:
        # determinism-audit gate (<= 3% step time with digest chain +
        # flight ring compiled in; identical chains across seeded reruns;
        # the bisector pinpoints a forged divergence)
        print(json.dumps(stage_audit_smoke()), flush=True)
        return
    if "--gear-smoke" in sys.argv:
        # occupancy-adaptive gearing gate (>= 25% per-window win with the
        # pool oversized 8x above steady-state occupancy)
        print(json.dumps(stage_gear_win()), flush=True)
        return
    if "--stages-50k" in sys.argv:
        # BASELINE config 4 rows: both synchronization modes, on the
        # global engine AND the islands runner (r5: optimistic×islands),
        # plus the undersized-pool spill-cost row (VERDICT r4 #6)
        print(json.dumps(stage_udp_flood_50k("conservative")), flush=True)
        print(json.dumps(stage_udp_flood_50k("optimistic")), flush=True)
        print(json.dumps(stage_udp_flood_50k(
            "conservative", num_shards=8)), flush=True)
        print(json.dumps(stage_udp_flood_50k(
            "optimistic", num_shards=8)), flush=True)
        print(json.dumps(stage_spill_50k()), flush=True)
        return

    import jax

    num_hosts, msgload, stop_s = 16384, 8, 10
    dev_events, dev_wall, sim_per_wall = device_phold(
        num_hosts, msgload, stop_s
    )
    dev_rate = dev_events / dev_wall if dev_wall > 0 else 0.0

    base = cpp_phold_baseline(num_hosts, msgload, stop_s)
    base_rate = base["events_per_sec"] or 1.0

    print(
        json.dumps(
            {
                "metric": "phold_committed_events_per_sec_per_chip",
                "value": round(dev_rate, 1),
                "unit": "events/sec",
                "vs_baseline": round(dev_rate / base_rate, 3),
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind,
                           "count": len(jax.devices())},
                "detail": {
                    "hosts": num_hosts,
                    "msgload": msgload,
                    "sim_seconds": stop_s,
                    "device_events": int(dev_events),
                    "device_wall_s": round(dev_wall, 3),
                    "sim_sec_per_wall_sec": round(sim_per_wall, 2),
                    "baseline": base,
                },
            }
        )
    )


if __name__ == "__main__":
    main()
