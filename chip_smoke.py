#!/usr/bin/env python3
"""Chip smoke: the device network stack on a TPU, through the package's
own entry points, in ONE process (a chip belongs to one process).

    python chip_smoke.py                # one chip: phases A-D
    python chip_smoke.py --four-chips   # four chips: the islands mesh path

Phases on one chip:

  A. UDP flood, BASELINE config 2: 10,240 hosts, 4 simulated seconds,
     through `build_simulation` / `Simulation.run` (event pool, NIC token
     buckets, CoDel router, UDP sockets stepped as window kernels).
  B. PHOLD flagship: 16,384 hosts, msgload 8, run to its stop time.
  C. Chip against CPU: A's and B's configurations again, at full size, on
     `jax.devices("cpu")[0]` in this process, run to the point 1 s into
     their traffic where A and B recorded their chip chains. The CPU arm
     is the reference (the kernels are pure integer programs, so the
     audit chain and committed-event count must be bit-identical), not a
     fallback. It runs in a thread beside A and B, mostly inside their
     TPU compiles. Full size rather than a smaller copy: a new size would
     cost two more TPU compiles of minutes each.
  D. Managed processes through the device network: the README's UDP echo
     pair, built with `cc`, run by `shadow_tpu.__main__.main(argv)` with
     `experimental.use_device_network: true` (the CPU<->TPU handoff of
     procs/bridge.py).

`--four-chips` runs only the mesh path and what it is compared with: a
40,960-host flood as four `shard_map` islands (ppermute exchange, min-cut
placement), printing each device's bytes in use, and its chain 0.2 s
into the traffic against the same configuration as `vmap` islands (one
program) on the CPU, in a thread beside the mesh run. The CPU arm costs
no TPU compile; a second mesh size or a one-chip vmap arm would cost
minutes of compile on four chips.

Every line but the last is a record (`# ...` progress or one JSON object
per phase). The last line is `{"ok": true, "device": {...}}` and is printed
only when every phase passed; any failure exits non-zero. When JAX finds no
TPU the script exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

_REPO = os.path.dirname(os.path.abspath(__file__))
NS_PER_SEC = 1_000_000_000

# full sizes (ISSUE 21); the tests call the phase functions at tiny sizes
FLOOD_HOSTS = 10_240
FLOOD_STOP_S = 4
FLOOD_WPD = 32
PHOLD_HOSTS = 16_384
PHOLD_MSGLOAD = 8
PHOLD_STOP_S = 10
PHOLD_WPD = 64
# phase C's comparison point: 1 s into the traffic (both apps start at 1 s)
PREFIX_NS = 2 * NS_PER_SEC
MESH_HOSTS = 40_960
MESH_POOL = 4 << 15  # phase A's 2^15 pool rows per chip
# 0.2 s into the traffic: 20 windows, cheap enough for the CPU reference
MESH_PREFIX_NS = 1_200_000_000
ECHO_LATENCY_MS = 25
ECHO_COUNT = 3


class PhaseFailed(AssertionError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    own monitoring events (jax._src.dispatch's /jax/core/compile/*)."""

    def __init__(self):
        import jax

        self.total_s = 0.0
        self.backend_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        # the chip's compiles only, not the reference arm's thread
        if threading.current_thread() is not threading.main_thread():
            return
        if event.startswith("/jax/core/compile/"):
            self.total_s += duration
            if event.endswith("backend_compile_duration"):
                self.backend_s += duration

    def read(self) -> tuple[float, float]:
        return self.total_s, self.backend_s


def _devices_of(sim) -> set:
    """The devices that hold `sim`'s state."""
    import jax

    return {d for leaf in jax.tree.leaves(sim.state)
            if hasattr(leaf, "devices") for d in leaf.devices()}


def _prefix(sim, until_ns: int, wpd: int) -> dict:
    """Run `sim` to `until_ns`; its audit chain and committed events."""
    sim.run(until=until_ns, windows_per_dispatch=wpd)
    return {"until_ns": until_ns, "chain": sim.audit_chain(),
            "events_committed": sim.counters()["events_committed"]}


def run_sim(sim, wpd: int, clock: CompileClock | None = None,
            prefix_ns: int | None = None) -> dict:
    """Run `sim` to its stop time and report what the checks read; with
    `prefix_ns`, also record the chain at that point on the way (phase
    C's comparison point)."""
    import jax
    import numpy as np

    c0 = clock.read() if clock else (0.0, 0.0)
    t0 = time.perf_counter()
    prefix = _prefix(sim, prefix_ns, wpd) if prefix_ns else None
    t1 = time.perf_counter()
    sim.run(windows_per_dispatch=wpd)
    jax.block_until_ready(sim.state)
    t2 = time.perf_counter()
    wall = t2 - t0
    c = sim.counters()
    out = {
        "hosts": sim.num_hosts,
        "devices": sorted(str(d) for d in _devices_of(sim)),
        "wall_s": wall,
        # start of the last window run (the laggard island's, on the
        # islands engine); that window ends one runahead on
        "last_window_end_ns": int(np.min(jax.device_get(sim.state.now)))
        + int(sim.runahead),
        "stop_ns": int(sim.stop_time),
        "events_committed": c["events_committed"],
        "packets_delivered": c.get("packets_delivered", 0),
        "pool_overflow_dropped": c["pool_overflow_dropped"],
        "drops": {k: v for k, v in c.items() if "dropped" in k and v},
        "chain": sim.audit_chain(),
    }
    if prefix:
        # the span after the prefix: compiled, so closer to steady state
        prefix["after_wall_s"] = t2 - t1
        out["prefix"] = prefix
    if clock:
        c1 = clock.read()
        out["compile_s"] = c1[0] - c0[0]
        out["backend_compile_s"] = c1[1] - c0[1]
    return out


def _reached_stop(r: dict) -> bool:
    return r["last_window_end_ns"] >= r["stop_ns"]


def flood_sim(num_hosts: int = FLOOD_HOSTS, stop_s: int = FLOOD_STOP_S,
              **experimental):
    from shadow_tpu.flagship import build_udp_flood

    return build_udp_flood(num_hosts, stop_s=stop_s, **experimental)


def phold_sim(num_hosts: int = PHOLD_HOSTS, msgload: int = PHOLD_MSGLOAD,
              stop_s: int = PHOLD_STOP_S):
    from shadow_tpu.flagship import build_phold_flagship

    return build_phold_flagship(num_hosts, msgload=msgload, stop_s=stop_s,
                                runtime_s=stop_s)


def phase_flood(num_hosts: int = FLOOD_HOSTS, stop_s: int = FLOOD_STOP_S,
                wpd: int = FLOOD_WPD, clock=None, prefix_ns=None,
                **experimental) -> dict:
    """A: the UDP flood through the full device network stack."""
    r = run_sim(flood_sim(num_hosts, stop_s, **experimental), wpd, clock,
                prefix_ns)
    _check(r["events_committed"] > 0, f"flood committed nothing: {r}")
    _check(r["packets_delivered"] > 0, f"flood delivered nothing: {r}")
    _check(r["pool_overflow_dropped"] == 0, f"flood overflowed: {r}")
    _check(_reached_stop(r), f"flood stopped short of stop_time: {r}")
    return r


def phase_phold(num_hosts: int = PHOLD_HOSTS, msgload: int = PHOLD_MSGLOAD,
                stop_s: int = PHOLD_STOP_S, wpd: int = PHOLD_WPD,
                clock=None, prefix_ns=None) -> dict:
    """B: the PHOLD flagship, run to its stop time."""
    r = run_sim(phold_sim(num_hosts, msgload, stop_s), wpd, clock,
                prefix_ns)
    _check(r["events_committed"] > num_hosts * msgload,
           f"phold committed too little: {r}")
    _check(not r["drops"], f"phold dropped events: {r}")
    _check(_reached_stop(r), f"phold stopped short of stop_time: {r}")
    return r


def reference_runs(cases: dict, until_ns: int, reference=None) -> dict:
    """Phase C's reference arm: each case is (build, wpd); `build` makes
    a configuration the chip ran, and it runs here to `until_ns` on
    `reference` (the CPU by default). Needs nothing from the chip runs,
    so main() runs it in a thread while they compile."""
    import jax

    reference = reference or jax.devices("cpu")[0]
    out = {}
    for name, (build, wpd) in cases.items():
        t0 = time.perf_counter()
        with jax.default_device(reference):
            sim = build()
            out[name] = _prefix(sim, until_ns, wpd)
        # the reference must really have run there, or C compares nothing
        held = _devices_of(sim)
        _check(held == {reference},
               f"{name}: the reference arm ran on {held}, not {reference}")
        out[name]["wall_s"] = time.perf_counter() - t0
        out[name]["platform"] = reference.platform
    return out


def phase_compare(chip: dict, reference: dict) -> dict:
    """C: each chip run (recorded with `prefix_ns`) against the reference
    arm's run of the same configuration to the same point: audit chain
    and committed events must be equal there."""
    out = {}
    for name, b in reference.items():
        _check("prefix" in chip[name],
               f"{name}: the chip run recorded no prefix")
        a = chip[name]["prefix"]
        _check(a["until_ns"] == b["until_ns"],
               f"{name}: compared at {a['until_ns']} and {b['until_ns']}")
        _check(a["chain"] == b["chain"],
               f"{name}: chain {a['chain']} != {b['chain']} on the "
               f"{b['platform']} reference")
        _check(a["events_committed"] == b["events_committed"],
               f"{name}: events_committed {a['events_committed']} != "
               f"reference {b['events_committed']}")
        out[name] = {"chip": a, "reference": b}
    return out


def _echo_yaml(server: str, client: str, data_dir: str) -> str:
    return f"""\
general:
  stop_time: 30 s
  seed: 12
  data_directory: {data_dir}
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 bandwidth_down "100 Mbit" bandwidth_up "100 Mbit" ]
        edge [ source 0 target 0 latency "{ECHO_LATENCY_MS} ms" ]
      ]
experimental:
  use_device_network: true
  event_capacity: 2048
  events_per_host_per_window: 8
hosts:
  server:
    processes:
      - path: {server}
        args: 9000 {ECHO_COUNT}
  client:
    processes:
      - path: {client}
        args: server 9000 {ECHO_COUNT}
        start_time: 1 s
"""


def phase_managed(workdir: str) -> dict:
    """D: the UDP echo pair through the device network, via the CLI's
    `main(argv)` in this process (a child would fight for the chip)."""
    from shadow_tpu.__main__ import main as shadow_main

    cc = shutil.which("cc") or shutil.which("gcc")
    _check(cc is not None, "no C compiler for the echo pair")
    bins = {}
    for name in ("udp_echo_server", "udp_echo_client"):
        bins[name] = os.path.join(workdir, name)
        subprocess.run(
            [cc, "-O1", "-o", bins[name],
             os.path.join(_REPO, "tests", "apps", f"{name}.c")],
            check=True, capture_output=True,
        )
    data_dir = os.path.join(workdir, "shadow.data")
    cfg = os.path.join(workdir, "echo.yaml")
    with open(cfg, "w") as f:
        f.write(_echo_yaml(bins["udp_echo_server"], bins["udp_echo_client"],
                           data_dir))
    t0 = time.perf_counter()
    rc = shadow_main([cfg])
    wall = time.perf_counter() - t0
    _check(rc == 0, f"shadow_tpu main returned {rc}")
    client_dir = os.path.join(data_dir, "hosts", "client")
    lines = []
    for name in sorted(os.listdir(client_dir)):
        if name.endswith(".stdout"):
            with open(os.path.join(client_dir, name)) as f:
                lines += f.read().splitlines()
    rtts = [int(l.split()[1]) for l in lines if l.startswith("rtt")]
    want = [2 * ECHO_LATENCY_MS * 1_000_000] * ECHO_COUNT
    _check(rtts == want, f"echo rtts {rtts} != {want}")
    return {"wall_s": wall, "rtt_ns": rtts}


def _mesh_experimental(mode: str) -> dict:
    return {"num_shards": 4, "island_mode": mode,
            "mesh_exchange": "ppermute", "placement": "min_cut"}


def mesh_sim(mode: str, num_hosts: int = MESH_HOSTS,
             stop_s: int = FLOOD_STOP_S, **experimental):
    """The flood as four islands, each with phase A's host count and pool:
    `shard_map` puts one per device, `vmap` batches them in one program."""
    experimental.setdefault("event_capacity", MESH_POOL)
    return flood_sim(num_hosts, stop_s, **_mesh_experimental(mode),
                     **experimental)


def phase_mesh(num_hosts: int = MESH_HOSTS, stop_s: int = FLOOD_STOP_S,
               clock=None, prefix_ns: int = MESH_PREFIX_NS,
               **experimental) -> dict:
    """Four chips: the shard_map flood, with each device's bytes in use."""
    import jax

    r = run_sim(mesh_sim("shard_map", num_hosts, stop_s, **experimental),
                FLOOD_WPD, clock, prefix_ns)
    _check(r["pool_overflow_dropped"] == 0, f"mesh flood overflowed: {r}")
    _check(_reached_stop(r), f"mesh flood stopped short of stop_time: {r}")
    r["bytes_in_use"] = {
        str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.devices()
    }
    return r


def _managed_in_tmp() -> dict:
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        return phase_managed(td)


def _emit(phase: str, record) -> None:
    print(json.dumps({"phase": phase, **record}, default=str), flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip islands mesh path")
    args = ap.parse_args(argv)

    # the package first: without it (a directory holding only this
    # script) the run fails here, before it touches a device
    from shadow_tpu.serve.kcache import enable_xla_cache

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} TPU devices, found {len(devs)}",
              file=sys.stderr)
        return 1
    print(f"# device {dev.platform} {dev.device_kind} x{len(devs)}, "
          f"jax {jax.__version__}", flush=True)

    root, _ = enable_xla_cache()
    print(f"# compile cache {root}", flush=True)
    clock = CompileClock()
    done = {}
    if args.four_chips:
        chip_phases = [("mesh_40960", lambda: phase_mesh(clock=clock))]
        ref_cases, ref_ns = {"mesh_40960": (
            functools.partial(mesh_sim, "vmap"), FLOOD_WPD)}, MESH_PREFIX_NS
        compare, after = "mesh_vs_vmap_cpu", []
    else:
        chip_phases = [
            ("A_flood_10240",
             lambda: phase_flood(clock=clock, prefix_ns=PREFIX_NS)),
            ("B_phold_16384",
             lambda: phase_phold(clock=clock, prefix_ns=PREFIX_NS)),
        ]
        ref_cases, ref_ns = {"A_flood_10240": (flood_sim, FLOOD_WPD),
                             "B_phold_16384": (phold_sim, PHOLD_WPD)}, \
            PREFIX_NS
        compare, after = "C_chip_vs_cpu", [("D_managed_echo",
                                            _managed_in_tmp)]
    failed = []
    t_all = time.perf_counter()
    # the CPU reference arm runs beside the chip phases (it needs nothing
    # from them), mostly inside their TPU compiles
    with ThreadPoolExecutor(max_workers=1) as pool:
        ref = pool.submit(reference_runs, ref_cases, ref_ns)
        phases = chip_phases + [
            (compare, lambda: phase_compare(done, ref.result())),
        ] + after
        for name, fn in phases:
            t0 = time.perf_counter()
            try:
                done[name] = fn()
                _emit(name, {"ok": True, "result": done[name],
                             "phase_wall_s": time.perf_counter() - t0})
            except Exception as e:  # every phase runs; any failure fails all
                failed.append(name)
                _emit(name, {"ok": False,
                             "error": f"{type(e).__name__}: {e}",
                             "phase_wall_s": time.perf_counter() - t0})
    print(f"# total wall {time.perf_counter() - t_all:.1f}s, compile "
          f"{clock.total_s:.1f}s; failed: {failed or 'none'}", flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
