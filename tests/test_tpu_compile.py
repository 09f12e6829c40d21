"""The main path's window kernels compile for a TPU v5e chip that is
DESCRIBED, not attached (ISSUE 21): the TPU compiler runs here and
refuses what the chip would refuse — a program that does not fit the
device's 16 GB, or a scatter (the engine's design bans them; they
serialize on TPU). Nothing runs, so this says nothing about results or
times; chip_smoke.py is the run on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file. The persistent compilation cache is off around these
compiles — an entry written for a described chip cannot be read back
here.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024**3
_SCATTER_OP = re.compile(r"=\s*\S+\s+scatter\(")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _described(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.result_type(x), sharding=sharding),
        tree,
    )


def _compile_window_step(sim, sharding):
    """Compile `sim`'s jitted window step (state, params, ws, we) for the
    described chip."""
    t = jax.ShapeDtypeStruct((), jnp.int64, sharding=sharding,
                             weak_type=True)
    return sim._step.lower(
        _described(sim.state, sharding), _described(sim.params, sharding),
        t, t,
    ).compile()


def _assert_fits_and_scatter_free(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, m
    text = compiled.as_text()
    assert not _SCATTER_OP.search(text), [
        ln for ln in text.splitlines() if _SCATTER_OP.search(ln)][:3]


def test_flagship_window_step_compiles_for_v5e(one_chip,
                                               no_persistent_cache):
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "__graft_entry__.py"))
    graft = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(graft)
    step, (state, params, ws, we) = graft.entry()
    t = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip,
                             weak_type=True)
    compiled = step.lower(
        _described(state, one_chip), _described(params, one_chip), t, t,
    ).compile()
    _assert_fits_and_scatter_free(compiled)


def test_udp_flood_window_step_compiles_for_v5e(one_chip,
                                                no_persistent_cache):
    """A small UDP-flood netstack sim: its window kernel carries the NIC
    token-bucket, CoDel router and UDP socket handlers."""
    from shadow_tpu.flagship import build_udp_flood

    sim = build_udp_flood(256, stop_s=2, event_capacity=4096)
    _assert_fits_and_scatter_free(_compile_window_step(sim, one_chip))
