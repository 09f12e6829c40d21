"""The window kernel's phases in the trace: each XLA op of a whole `run_to`
execution filed under the innermost `win.*` named scope of its op_name
(core/engine.py make_window_step: win.extract, win.handlers, win.commit,
win.merge), its self time summed per phase, per simulated window.

JAX's `ProfileData` gives each op event its HLO text, not its metadata.
On a TPU the device plane keeps each op's op_name in the op's event
metadata, as the `tf_op` stat beside its `program_id`; the module events
are named `jit_run_to(<program id>)`. So this module reads the event
metadata of the device planes from the `.xplane.pb` itself: the protobuf
wire format of XSpace, of which it decodes only the fields named below.

A fused op carries the op_name of its fusion's root, so an op whose
fusion straddles two phases is counted in its root's. An op the compiler
made carries none, and is counted in the phase of the op before it (see
`self_times`). A program built without the scopes has no `win.*`
op_name; then every reading is None.
"""

from __future__ import annotations

import bisect
import functools
import os
import re

from benchmark import run as run_mod, tracing, window_kernel

PHASES = ("win.extract", "win.handlers", "win.commit", "win.merge")
UNSCOPED = "unscoped"
# ops that hold the ops of their body on the same trace line
CONTROL = ("while", "conditional")
# XSpace field numbers (tsl/profiler/protobuf/xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2  # of a map entry
META_ID, META_NAME, META_STATS = 1, 2, 5  # of event and stat metadata
STAT_METADATA_ID, STAT_UINT64, STAT_INT64, STAT_STR = 1, 3, 4, 5
PROGRAM = re.compile(r"\((\d+)\)$")


def _varint(buf, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: a varint as an
    int, a length-delimited field as a memoryview of its bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val = buf[pos:pos + n]
            pos += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val = buf[pos:pos + n]
            pos += n
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, val


def _map_values(buf):
    for num, val in _fields(buf):
        if num == MAP_VALUE:
            return val
    return b""


def _event_metadata(plane) -> list[tuple[str, dict]]:
    """(name, {stat name: value}) of each event metadata of a plane."""
    stat_names: dict[int, str] = {}
    metas = []
    for num, val in _fields(plane):
        if num == PLANE_STAT_METADATA:
            f = dict(_fields(_map_values(val)))
            stat_names[f.get(META_ID, 0)] = bytes(
                f.get(META_NAME, b"")).decode()
        elif num == PLANE_EVENT_METADATA:
            metas.append(_map_values(val))
    out = []
    for m in metas:
        name, stats = "", []
        for num, val in _fields(m):
            if num == META_NAME:
                name = bytes(val).decode(errors="replace")
            elif num == META_STATS:
                stats.append(dict(_fields(val)))
        vals = {}
        for st in stats:
            key = stat_names.get(st.get(STAT_METADATA_ID, -1))
            for n in (STAT_STR, STAT_UINT64, STAT_INT64):
                if n in st:
                    v = st[n]
                    vals[key] = bytes(v).decode(errors="replace") \
                        if n == STAT_STR else v
        out.append((name, vals))
    return out


def op_scopes(xspace: bytes, module: str = window_kernel.MODULE
              ) -> dict[str, dict[str, str]]:
    """{device plane: {op: op_name}} for the ops of the programs whose
    module name contains `module`; `op` is the short name the `breakdown`
    uses (`fusion.10`)."""
    out = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != SPACE_PLANES:
            continue
        name = next((bytes(v).decode() for n, v in _fields(plane)
                     if n == PLANE_NAME), "")
        if not tracing.DEVICE_PLANE.match(name):
            continue
        metas = _event_metadata(plane)
        programs = {int(m.group(1)) for n, _ in metas if module in n
                    for m in [PROGRAM.search(n)] if m}
        out[name] = {tracing.short(n): st["tf_op"].rstrip(":")
                     for n, st in metas
                     if "tf_op" in st and st.get("program_id") in programs}
    return out


def phase(op_name: str | None) -> str:
    """The innermost `win.*` component of an op_name, else UNSCOPED."""
    inner = [c for c in (op_name or "").split("/") if c in PHASES]
    return inner[-1] if inner else UNSCOPED


def self_times(ops, scopes: dict[str, str]) -> list[tuple[str, str, int]]:
    """(op, phase, self ns) of each event, as tracing.self_times counts
    self time. An op the compiler made (a layout copy, a rewritten scan)
    has no op_name: it is filed in the phase of the op that ran before it.
    A `while` or `conditional` holding other ops is the loop's control,
    unscoped whatever its op_name."""
    out = []
    stack: list[list] = []  # [op, phase, end, self time]
    last = UNSCOPED
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            op, ph, _, d = stack.pop()
            out.append((op, ph, d))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        if name.startswith(CONTROL):
            ph = UNSCOPED
        elif name in scopes:
            ph = last = phase(scopes[name])
        else:
            ph = last
        stack.append([name, ph, e, e - s])
    out += [(op, ph, d) for op, ph, _, d in stack]
    return out


def split(ops, scopes: dict[str, str], executions) -> list[dict]:
    """Per execution, {phase: self ns} of the ops inside it. `ops`: one
    device's [(op, start_ns, end_ns)]; `scopes`: {op: op_name}."""
    ops = sorted(ops, key=lambda ev: ev[1])
    starts = [s for _, s, _ in ops]
    out = []
    for x in executions:
        i, j = bisect.bisect_left(starts, x.start), bisect.bisect_right(
            starts, x.end)
        per = {p: 0 for p in PHASES + (UNSCOPED,)}
        mine = tracing.clip_events(ops[i:j], x.start, x.end)
        for _, ph, d in self_times(mine, scopes):
            per[ph] += d
        out.append(per)
    return out


def reduce_planes(planes, scopes, executions) -> dict[str, float] | None:
    """{phase: mean ns per execution} over the device planes, or None where
    no op of the program carries a `win.*` scope."""
    if not any(phase(n) != UNSCOPED for sc in scopes.values()
               for n in sc.values()):
        return None
    total = {p: 0.0 for p in PHASES + (UNSCOPED,)}
    devices = 0
    for name, lines in planes:
        if not tracing.DEVICE_PLANE.match(name) or name not in scopes:
            continue
        devices += 1
        per = split(lines.get(tracing.OPS_LINE, []), scopes[name],
                    executions)
        for p in total:
            total[p] += sum(x[p] for x in per) / len(per)
    if not devices:
        return None
    return {p: v / devices for p, v in total.items()}


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime: float, executions: tuple) -> dict | None:
    """reduce_planes of one trace file; the five readers of a run share
    the one reading."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        sc = op_scopes(f.read())
    pd = ProfileData.from_file(path)
    planes = [(p.name, {tracing.OPS_LINE: tracing._events(ln)
                        for ln in p.lines if ln.name == tracing.OPS_LINE})
              for p in pd.planes if tracing.DEVICE_PLANE.match(p.name)]
    return reduce_planes(planes, sc,
                         [tracing.Execution(*x) for x in executions])


def per_execution_ns(run) -> dict[str, float] | None:
    """{phase: mean device ns per whole `run_to` execution} of the traced
    run's newest trace."""
    ex = run.trace.executions(window_kernel.MODULE) if run.trace else []
    if not ex:
        return None
    trace_dir = str(run.spec.root / run_mod.CACHE / "trace")
    try:
        path = tracing.newest_xplane(trace_dir)
    except FileNotFoundError:
        return None
    return _read(path, os.path.getmtime(path),
                 tuple((x.start, x.end, x.busy_ns) for x in ex))


def ms_per_window(run, name: str) -> float | None:
    """Device ms of one phase (or UNSCOPED) per simulated window."""
    per = per_execution_ns(run)
    w = window_kernel.per_dispatch(run, "windows")
    if not per or not w:
        return None
    return per[name] / 1e6 / w
