"""The comparison that decides `correct`: what the timed run left in the
program's state at the window's last frontier, against what the plain
reference (benchmark/reference/<app model>.py) says every host must hold
there.

The simulator is an integer program, so every number is exact and every
limit is 0. Three numbers are compared:

- `host_values_off`: (host, field) pairs of the reference's FIELDS whose
  value differs;
- `pending_off`: hosts whose count of pending events differs;
- `totals_off`: the summed absolute gap of the reference's TOTALS.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"host_values_off": 0, "pending_off": 0, "totals_off": 0}
INT64_MAX = np.iinfo(np.int64).max


def _get(obj, path: str):
    for part in path.split("."):
        obj = obj[part] if isinstance(obj, dict) else getattr(obj, part)
    return np.asarray(obj)


def _read(state, expr: str) -> np.ndarray:
    """A dotted path into the state, or the difference of two."""
    a, _, b = expr.partition(" - ")
    v = _get(state, a).astype(np.int64)
    if b:
        v = v - _get(state, b).astype(np.int64)
    # per-host tables with a slot axis ([H, sockets]) compare as host sums
    return v.reshape(v.shape[0], -1).sum(axis=1) if v.ndim > 1 else v


def capacity(state, ref) -> dict:
    """The reference's CAPACITY counters in the program's state, each
    summed over hosts. Reads those leaves alone, and nothing where the
    reference declares none."""
    return {p: int(np.sum(_read(state, p))) for p in ref.CAPACITY}


def observe(state, ref) -> dict:
    """What the reference predicts, read from the program's state."""
    import jax

    state = jax.device_get(state)
    out = {k: _read(state, p) for k, p in ref.FIELDS.items()}
    out.update({k: int(_read(state, p)) for k, p in ref.TOTALS.items()})
    t = np.asarray(state.pool.time).reshape(-1)
    d = np.asarray(state.pool.dst).reshape(-1)
    H = next(iter(out.values())).shape[0]
    out["pending"] = np.bincount(d[t != INT64_MAX], minlength=H)[:H]
    return out


def compare(got: dict, want: dict, ref) -> dict:
    """Each compared number: {"value": ..., "limit": ...}."""
    host = sum(int(np.sum(np.asarray(got[k], np.int64)
                          != np.asarray(want[k], np.int64)))
               for k in ref.FIELDS)
    pend = int(np.sum(np.asarray(got["pending"], np.int64)
                      != np.asarray(want["pending"], np.int64)))
    tot = sum(abs(int(got[k]) - int(want[k])) for k in ref.TOTALS)
    vals = {"host_values_off": host, "pending_off": pend, "totals_off": tot}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in vals.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
