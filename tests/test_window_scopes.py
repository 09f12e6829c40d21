"""The window kernel's phases as named scopes in the traced `run_to`
program (the op_name a profiler shows for each op): each of `win.extract`,
`win.handlers`, `win.commit` and `win.merge` holds ops, and the sorts and
the `_take_rows` gathers lie under extraction or the merge; and no row
gather moves 64-bit elements, which the TPU gathers one half-word at a
time."""

from __future__ import annotations

import jax
import pytest

from shadow_tpu.core import engine
from shadow_tpu.flagship import SELF_LOOP_50MS_GML, build_phold_flagship
from shadow_tpu.sim import build_simulation

PHASES = ("win.extract", "win.handlers", "win.commit", "win.merge")
# the scope a test puts around `_take_rows`, to tell its gathers apart
TAKE_ROWS = "take_rows"


def _udp_loop_sim():
    # a NetStack model has no matrix handler: every window takes the
    # micro-step loop path
    return build_simulation({
        "general": {"stop_time": "1 s", "seed": 4},
        "network": {"graph": {"type": "gml", "inline": SELF_LOOP_50MS_GML}},
        "experimental": {"event_capacity": 1024,
                         "events_per_host_per_window": 8},
        "hosts": {
            "server": {"app_model": "udp_flood",
                       "app_options": {"role": "server"}},
            "client": {"quantity": 7, "app_model": "udp_flood",
                       "app_options": {"interval": "50 ms", "size": 400,
                                       "runtime": 1}},
        },
    })


BUILDS = {
    # the global engine: a lax.cond between the matrix and loop paths
    "phold": lambda: build_phold_flagship(
        32, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=2048),
    # islands pin the matrix path; the merge routes through the exchange
    "phold_islands": lambda: build_phold_flagship(
        32, msgload=2, stop_s=2, runtime_s=2, seed=3, event_capacity=2048,
        num_shards=2, exchange_slots=16),
    "udp_loop": _udp_loop_sim,
}


def _leaf_ops(jaxpr, stack=()):
    """(equation, name-stack components) of every equation that holds no
    sub-program, with the stacks of the equations that hold it."""
    for eqn in jaxpr.eqns:
        here = stack + tuple(str(eqn.source_info.name_stack).split("/"))
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if not subs:
            yield eqn, here
        for sub in subs:
            yield from _leaf_ops(sub, here)


def _phase(stack) -> str | None:
    inner = [c for c in stack if c in PHASES]
    return inner[-1] if inner else None


@pytest.fixture(scope="module", params=sorted(BUILDS))
def run_to_ops(request, monkeypatch_module):
    take_rows = engine._take_rows

    def marked(cols, perm):
        with jax.named_scope(TAKE_ROWS):
            return take_rows(cols, perm)

    monkeypatch_module.setattr(engine, "_take_rows", marked)
    sim = BUILDS[request.param]()
    closed = jax.make_jaxpr(sim._run_to)(sim.state, sim.params, 10**9, 4)
    return request.param, list(_leaf_ops(closed.jaxpr))


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


def test_each_phase_names_ops(run_to_ops):
    name, ops = run_to_ops
    per = {p: 0 for p in PHASES}
    for _, stack in ops:
        ph = _phase(stack)
        if ph:
            per[ph] += 1
    assert all(per.values()), (name, per)


def test_sorts_and_row_gathers_lie_in_extract_or_merge(run_to_ops):
    name, ops = run_to_ops
    sorts = [_phase(s) for e, s in ops if e.primitive.name == "sort"]
    rows = [_phase(s) for e, s in ops
            if e.primitive.name == "gather" and TAKE_ROWS in s]
    assert len(sorts) >= 3 and len(rows) >= 2, (name, sorts, rows)
    assert set(sorts) | set(rows) <= {"win.extract", "win.merge"}
    assert {"win.extract", "win.merge"} <= set(sorts)


def test_row_gathers_move_32_bit_words(run_to_ops):
    """Every row gather (operand of rank >= 2) moves 32-bit elements: the
    TPU lowers a 64-bit gather as one index pass per half-word, so
    `_take_rows` gathers its rows as int32 words. 1-D table lookups are
    not row moves."""
    name, ops = run_to_ops
    rows = [e.invars[0].aval for e, _ in ops
            if e.primitive.name == "gather" and e.invars[0].aval.ndim >= 2]
    assert rows, name
    wide = [a.str_short() for a in rows if a.dtype.itemsize > 4]
    assert not wide, (name, wide)
