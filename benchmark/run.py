"""One run of one benchmark cell, in one process, on the cell's chips.

    python3 -m benchmark --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's simulation with `shadow_tpu.sim.build_simulation`
(the entry `python -m shadow_tpu` uses) and runs it to the mix's
`warm_until`, which compiles or loads every program the window drives.
The window is ONE `Simulation.run` call toward a stop time far beyond
reach; a handoff hook closes it at the first dispatch boundary after
`--seconds` of wall time. Then the program's state at that boundary is
compared with the plain reference (compare.py), and the last line of
standard output is the result:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
     "checks"}

`--trace 0` reports the end-to-end metrics; `--trace 1` traces a few
dispatches inside the window with JAX's profiler and reports the
per-layer metrics, each read by layers/<metric>.py. Without a TPU, or
with fewer chips than the cell asks for, the run exits 2 and prints no
result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

from benchmark import compare, spec as spec_mod, traffic

# inside the checkout, at a fixed path: JAX's compile cache and the trace
CACHE = ".bench_cache"
# the traced span: at least this many dispatch boundaries and seconds
TRACE_BOUNDARIES = 4
TRACE_MIN_S = 1.0


class NoDevice(RuntimeError):
    pass


class WindowClosed(Exception):
    """Raised by the handoff hook to end the window's `run` call."""


class CompileClock:
    """JAX's compile events (`/jax/core/compile/*`, which also fire when a
    program is loaded from the persistent cache) and cache misses, on any
    thread."""

    def __init__(self):
        import jax

        self.total_s = 0.0
        self.programs = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total_s += duration
            if event.endswith("backend_compile_duration"):
                self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class SpanRecorder:
    """Stands where the engine looks for an `ObsSession` in the traced
    run: keeps the driver's spans (name, start, end on the host clock) and
    marks each in the profiler's trace. Unlike `ObsSession.round_done` it
    reads nothing from the device at a boundary."""

    tracer = None

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str, **_args):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def round_done(self, sim, frontier_ns=None) -> None:
        pass


@dataclass
class Boundary:
    t: float  # host clock
    frontier_ns: int
    events: int = 0  # committed so far (traced span only)
    windows: int = 0  # windows run so far (traced span only)


@dataclass
class Window:
    """The handoff hook that times the window and closes it."""

    seconds: float
    trace_dir: str | None
    t0: float = 0.0
    open: bool = False
    warm: list = field(default_factory=list)
    boundaries: list = field(default_factory=list)
    traced: list = field(default_factory=list)
    tracing: bool = False

    def __call__(self, sim, mn: int) -> None:
        now = time.perf_counter()
        if not self.open:
            self.warm.append(int(mn))
            return
        self.boundaries.append(Boundary(now, int(mn)))
        if self.trace_dir:
            self._trace_step(sim, now, int(mn))
        elif now - self.t0 >= self.seconds:
            raise WindowClosed

    def _trace_step(self, sim, now: float, mn: int) -> None:
        import jax

        from benchmark import tracing

        # committed state only: the dispatch in flight is not waited on
        ev = int(jax.device_get(sim.state.counters.events_committed))
        win = int(jax.device_get(sim.state.obs.win)[0])
        b = Boundary(now, mn, ev, win)
        if not self.tracing:
            tracing.start(self.trace_dir)
            self.tracing = True
            self.traced.append(b)
            return
        self.traced.append(b)
        if (len(self.traced) > TRACE_BOUNDARIES
                and now - self.traced[0].t >= TRACE_MIN_S):
            tracing.stop()
            self.tracing = False
            raise WindowClosed


@dataclass
class RunRecord:
    """What the per-layer readers read (layers/<metric>.py: read(run))."""

    cell: dict
    spec: object
    device_kind: str
    setup_s: float
    build_s: float
    compile_s: float
    spans: list
    traced: list  # Boundary per dispatch boundary of the traced span
    trace: object  # tracing.TraceSummary


def require_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"needs {chips} TPU chips, JAX found {len(devs)}")
    return devs


def _configure_cache(path: str) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _app_model(cfg: dict) -> str:
    (model,) = {h["app_model"] for h in cfg["hosts"].values()}
    return model


def operations(before: dict, after: dict, cap_before: dict,
               cap_after: dict) -> tuple[int, int]:
    """(attempted, failed) over the window: events committed plus those
    dropped for capacity, and the drops. The drops are the engine's pool
    and outbox drops (its counters `before` and `after`) and the growth
    of the reference's CAPACITY counters (`compare.capacity` at each
    end). The model's own packet loss is a result, and a deferral is no
    failure."""
    def d(key):
        return int(after[key]) - int(before[key])

    failed = (d("pool_overflow_dropped") + d("outbox_overflow_dropped")
              + sum(cap_after[k] - cap_before[k] for k in cap_after))
    return d("events_committed") + failed, failed


def sim_rate(win: "Window", f0: int) -> float:
    """Simulated seconds per wall second over the whole window: from its
    start to the dispatch boundary that closed it."""
    end = win.boundaries[-1]
    return (end.frontier_ns - f0) / 1e9 / (end.t - win.t0)


def measure(cell_name: str, seed: int, seconds: float, trace: bool,
            t0: float, spec=None, devices=require_devices) -> dict:
    """Run the cell once and return the result line's object."""
    spec = spec or spec_mod.Spec.load()
    cell = spec.cell(cell_name)
    mix = spec.traffic(cell)
    cfg = traffic.apply(spec.config(cell), mix, seed)
    ref = spec.reference(_app_model(cfg))
    cache = spec.root / CACHE
    # the program takes the cache the benchmark gives it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    import jax

    devs = devices(cell["chips"])
    dev = devs[0]
    _configure_cache(str(cache / "jax"))
    trace_dir = str(cache / "trace") if trace else None
    clock = CompileClock()
    from shadow_tpu.sim import build_simulation

    tb = time.perf_counter()
    sim = build_simulation(cfg)
    jax.block_until_ready(sim.state)
    build_s = time.perf_counter() - tb
    wpd = traffic.windows_per_dispatch(mix)
    win = Window(seconds, trace_dir)
    sim.add_handoff_hook(win)
    sim.run(until=traffic.warm_until_ns(mix), windows_per_dispatch=wpd)
    jax.block_until_ready(sim.state)
    before = sim.counters()
    cap_before = compare.capacity(sim.state, ref)
    f0 = win.warm[-1]
    programs0 = clock.programs
    compile_s = clock.total_s
    recorder = SpanRecorder() if trace else None
    sim.obs_session = recorder
    win.open = True
    win.t0 = time.perf_counter()
    setup_s = win.t0 - t0
    try:
        sim.run(windows_per_dispatch=wpd)
        # the traffic ran out before the clock closed the window: a
        # different result, with no frontier the reference can reach
        ended = 1
    except WindowClosed:
        ended = 0
    if win.tracing:
        from benchmark import tracing

        tracing.stop()
    end = win.boundaries[-1]
    wall = end.t - win.t0
    window_programs = clock.programs - programs0
    after = sim.counters()
    cap_after = compare.capacity(sim.state, ref)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    got = compare.observe(sim.state, ref)
    sim.obs_session = None
    del sim
    gc.collect()
    attempted, failed = operations(before, after, cap_before, cap_after)
    out = {
        "correct": False,
        "attempted": attempted,
        "failed": failed,
        "metrics": {},
        "device": device,
    }
    unit = {m["name"]: m["unit"] for m in spec.metrics(trace, cell)}
    if not trace:
        values = {"sim_s_per_wall_s": sim_rate(win, f0), "setup_s": setup_s}
    else:
        from benchmark import tracing

        summary = tracing.read(trace_dir)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
        rec = RunRecord(cell, spec, dev.device_kind, setup_s, build_s,
                        compile_s, recorder.spans, win.traced, summary)
        values = {name: spec.reader(name)(rec) for name in unit}
    out["metrics"] = {k: {"value": v, "unit": unit[k]}
                      for k, v in values.items() if k in unit and v is not None}
    checks = {"traffic_ended": {"value": ended, "limit": 0}}
    tr = time.perf_counter()
    if not ended:
        checks.update(compare.compare(
            got, ref.expected(cfg, end.frontier_ns), ref))
    ref_s = time.perf_counter() - tr
    checks["window_compiles"] = {"value": window_programs, "limit": 0}
    out["correct"] = compare.passed(checks)
    out["checks"] = checks
    ts = [win.t0] + [b.t for b in win.boundaries]
    gaps = [b - a for a, b in zip(ts, ts[1:])]
    longest = max(range(len(gaps)), key=gaps.__getitem__)
    print(f"# {cell_name} seed {seed}: build {build_s:.3f} s, compile "
          f"{compile_s:.3f} s ({clock.misses} cache misses), setup "
          f"{setup_s:.3f} s, window {wall:.3f} s, frontier {f0} -> "
          f"{end.frontier_ns} ns, {len(win.boundaries)} boundaries, "
          f"dispatch s median {sorted(gaps)[len(gaps) // 2]:.4f} max "
          f"{gaps[longest]:.4f} (dispatch {longest}), reference "
          f"{ref_s:.3f} s", file=sys.stderr)
    return out


def main(argv: list[str] | None = None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = measure(args.workload, args.seed, args.seconds,
                      bool(args.trace), t0)
    except NoDevice as e:
        print(f"benchmark: {e}; nothing was run", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
