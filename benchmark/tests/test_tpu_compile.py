"""The references' device programs compile for a described TPU v5e at the
cells' sizes (no chip needed): the compiler there refuses programs that
the CPU runs, as an int64 cumulative sum once was."""

from __future__ import annotations

import os

import pytest

H = 16384


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("msgload", [8, 1])
def test_reference_programs_compile_for_v5e(one_chip, msgload):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import phold

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    N = H * msgload
    phold._waves.lower(
        H, s((2,), jnp.uint32), s((N,), jnp.int32), s((H,), jnp.uint32),
        s((H,), jnp.int64), s((H,), jnp.int64), s((N,), bool),
        s((), jnp.int64), s((), jnp.int64), s((), jnp.float32),
        jnp.float32).compile()
