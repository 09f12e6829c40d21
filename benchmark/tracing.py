"""The profiler trace of a few dispatches inside the window, and its
reduction to what the per-layer readers and the `breakdown` read.

The trace is JAX's profiler (`jax.profiler.start_trace`), read back with
`jax.profiler.ProfileData`. On a TPU the device plane `/device:TPU:<n>`
has a line of XLA modules (one event per program execution) and a line of
XLA ops (one event per operation); the host plane carries the driver's
spans, which the traced run wraps in `TraceAnnotation`s so they share the
device's clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the driver's spans (core/engine.py Simulation.run)
HOST_SPANS = ("dispatch", "await", "issue", "host_drain", "spill")
TOP = 10


def start(trace_dir: str) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    jax.profiler.start_trace(trace_dir)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(merged: list[tuple[int, int]]) -> int:
    return sum(e - s for s, e in merged)


@dataclass
class Execution:
    start: int
    end: int
    busy_ns: int  # union of the ops that ran inside it


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int
    device_ops: list = field(default_factory=list)  # [[name, s], ...]
    idle_gaps: list = field(default_factory=list)  # [[label, s], ...]
    modules: dict = field(default_factory=dict)  # name -> [Execution]

    def executions(self, pattern: str) -> list[Execution]:
        """Executions of the modules whose name contains `pattern`, in
        time order, without the first and the last (the trace may cut
        them)."""
        ex = sorted((x for name, xs in self.modules.items()
                     if pattern in name for x in xs),
                    key=lambda x: x.start)
        return ex[1:-1]


def short(name: str) -> str:
    """An XLA op's event carries its whole HLO text; keep the op's name
    (`%fusion.12 = (...) fusion(...)` -> `fusion.12`)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    return [(short(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce_planes(planes) -> TraceSummary:
    """`planes`: [(plane name, {line name: [(event, start_ns, end_ns)]})]."""
    host = []
    devices = []
    for name, lines in planes:
        if DEVICE_PLANE.match(name):
            devices.append(lines)
        elif name.startswith("/host:"):
            for evs in lines.values():
                host += [ev for ev in evs if ev[0] in HOST_SPANS]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    host.sort(key=lambda ev: ev[1])
    # the traced span: from the first to the last driver span on the host,
    # or the device's own extent when the host recorded none
    all_ops = [ev for lines in devices for ev in lines.get(OPS_LINE, [])]
    if not all_ops:
        raise ValueError("the device plane has no XLA ops")
    lo = host[0][1] if host else min(ev[1] for ev in all_ops)
    hi = max(ev[2] for ev in host) if host else max(ev[2] for ev in all_ops)
    busy = 0
    per_op: dict[str, int] = {}
    gaps = []
    modules: dict[str, list[Execution]] = {}
    for lines in devices:
        ops = sorted(lines.get(OPS_LINE, []), key=lambda ev: ev[1])
        merged = union([(s, e) for _, s, e in ops])
        inside = clip(merged, lo, hi)
        busy += covered(inside)
        for name, d in self_times(clip_events(ops, lo, hi)):
            per_op[name] = per_op.get(name, 0) + d
        edges = [lo] + [t for iv in inside for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, _label(host, (g0 + g1) // 2)))
        starts = [s for _, s, _ in ops]
        for name, s, e in lines.get(MODULES_LINE, []):
            i, j = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
            mine = union([(max(a, s), min(b, e)) for _, a, b in ops[i:j]])
            modules.setdefault(name, []).append(Execution(s, e, covered(mine)))
    n = len(devices)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[0])
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / n / 1e9,
        devices=n,
        device_ops=[[k, v / 1e9] for k, v in top],
        idle_gaps=[[lab, g / 1e9] for g, lab in gaps[:TOP]],
        modules=modules,
    )


def clip_events(events, lo: int, hi: int):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def self_times(ops) -> list[tuple[str, int]]:
    """Each op's time less the ops nested inside it: a `while` or a
    `conditional` holds the ops of its body on the same line."""
    out = []
    stack: list[list] = []  # [name, end, self time]
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out += [tuple(op[::2]) for op in stack]
    return out


def _label(host, t: int) -> str:
    """The driver span the host was in at time t, or "none"."""
    for name, s, e in host:
        if s <= t < e:
            return name
        if s > t:
            break
    return "none"


def read(trace_dir: str) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(trace_dir))
    planes = [(p.name, {ln.name: _events(ln) for ln in p.lines})
              for p in pd.planes
              if DEVICE_PLANE.match(p.name) or p.name.startswith("/host:")]
    return reduce_planes(planes)
