"""Breaks of the UDP flood's timed path that the comparison must catch."""

from __future__ import annotations


def codel_drop_missed(monkeypatch):
    """In drop mode CoDel never drops: only the drop that enters it."""
    from shadow_tpu.net import codel

    monkeypatch.setattr(codel, "DROP_UNROLL", 0)


def deep_ring_truncated(monkeypatch):
    """The deep router ring is built with 16 slots, whatever the
    configuration gives it."""
    from shadow_tpu.net import codel

    real = codel.init

    def init(*args, deep_slots=0, **kw):
        return real(*args, deep_slots=min(deep_slots, 16), **kw)

    monkeypatch.setattr(codel, "init", init)


def starts_in_lockstep(monkeypatch):
    """Every client starts at start_time, the spread ignored."""
    from shadow_tpu import sim

    monkeypatch.setattr(sim, "_start_offsets", lambda *args: None)


FAULTS = {"codel_drop_missed": codel_drop_missed,
          "deep_ring_truncated": deep_ring_truncated,
          "starts_in_lockstep": starts_in_lockstep}
