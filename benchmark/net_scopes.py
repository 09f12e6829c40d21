"""The network stack's layers inside the window kernel's handlers, as the
trace shows them: each op of a whole `run_to` execution filed under the
innermost `net.*` named scope of its op_name (net/stack.py: net.nic,
net.router, net.udp), its self time summed per scope, per simulated
window.

The op names, the executions and the self-time rule are scopes.py's: an op
the compiler made carries no op_name and is filed with the op that ran
before it, and a `while` or `conditional` holding other ops is the loop's
control. The `net.*` scopes sit inside `win.handlers`, so they split that
phase and leave the `kernel.*` readings as they are. A program built
without them has no `net.*` op_name; then every reading is None.
"""

from __future__ import annotations

import bisect
import functools
import os

from benchmark import run as run_mod, scopes, tracing, window_kernel

NET = ("net.nic", "net.router", "net.udp")


def layer(op_name: str | None) -> str | None:
    """The innermost `net.*` component of an op_name, else None."""
    inner = [c for c in (op_name or "").split("/") if c in NET]
    return inner[-1] if inner else None


def self_times(ops, names: dict[str, str]) -> dict[str | None, int]:
    """{net layer or None: self ns} of the ops of one execution."""
    out: dict[str | None, int] = {}
    stack: list[list] = []  # [layer, end, self time]
    last = None
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            lay, _, d = stack.pop()
            out[lay] = out.get(lay, 0) + d
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        if name.startswith(scopes.CONTROL):
            lay = None
        elif name in names:
            lay = last = layer(names[name])
        else:
            lay = last
        stack.append([lay, e, e - s])
    for lay, _, d in stack:
        out[lay] = out.get(lay, 0) + d
    return out


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime: float, executions: tuple) -> dict | None:
    """{layer: mean device ns per execution} of one trace file, or None
    where no op of the program carries a `net.*` scope; the readers of a
    run share the one reading."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        names = scopes.op_scopes(f.read())
    if not any(layer(n) for sc in names.values() for n in sc.values()):
        return None
    total = {lay: 0.0 for lay in NET}
    devices = 0
    for p in ProfileData.from_file(path).planes:
        if not tracing.DEVICE_PLANE.match(p.name) or p.name not in names:
            continue
        ops = sorted((ev for ln in p.lines if ln.name == tracing.OPS_LINE
                      for ev in tracing._events(ln)), key=lambda ev: ev[1])
        starts = [s for _, s, _ in ops]
        per = []
        for start, end, _ in executions:
            i = bisect.bisect_left(starts, start)
            j = bisect.bisect_right(starts, end)
            per.append(self_times(tracing.clip_events(ops[i:j], start, end),
                                  names[p.name]))
        devices += 1
        for lay in NET:
            total[lay] += sum(x.get(lay, 0) for x in per) / len(per)
    if not devices:
        return None
    return {lay: v / devices for lay, v in total.items()}


def ms_per_window(run, name: str) -> float | None:
    """Device ms of the ops under one `net.*` scope per simulated window."""
    ex = run.trace.executions(window_kernel.MODULE) if run.trace else []
    w = ex and window_kernel.per_dispatch(run, "windows")
    if not w:
        return None
    try:
        path = tracing.newest_xplane(
            str(run.spec.root / run_mod.CACHE / "trace"))
    except FileNotFoundError:
        return None
    per = _read(path, os.path.getmtime(path),
                tuple((x.start, x.end, x.busy_ns) for x in ex))
    if per is None:
        return None
    return per[name] / 1e6 / w
