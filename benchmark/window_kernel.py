"""The window kernel as the trace shows it: executions of the jitted
`run_to` program (core/engine.py, one per dispatch), and the work the
counters say those dispatches did."""

from __future__ import annotations

# the XLA module of the fused window loop: jax.jit(run_to)
MODULE = "run_to"


def seconds_per_dispatch(run) -> float | None:
    """Mean device-busy seconds of one whole `run_to` execution."""
    ex = run.trace.executions(MODULE) if run.trace else []
    if not ex:
        return None
    return sum(x.busy_ns for x in ex) / len(ex) / 1e9


def per_dispatch(run, what: str) -> float | None:
    """Windows or committed events per dispatch over the traced span,
    from the counters read at its dispatch boundaries."""
    b = run.traced
    if len(b) < 2:
        return None
    return (getattr(b[-1], what) - getattr(b[0], what)) / (len(b) - 1)
