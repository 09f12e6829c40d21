"""Test fixtures: force an 8-device virtual CPU platform.

The tests run on the CPU: the sharding layer is validated on a virtual
8-device CPU mesh exactly as the driver's dryrun_multichip does. The
chip is reached only through chip_smoke.py; tests/test_tpu_compile.py
compiles for a described (not attached) TPU.
"""

from shadow_tpu.parallel.virtualize import force_cpu_devices

jax = force_cpu_devices(8)

import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402

import pytest  # noqa: E402

APPS_SRC = pathlib.Path(__file__).parent / "apps"

# Budgeted-run ordering: the full suite costs a multiple of the tier-1
# wall budget (XLA compiles dominate), so CI kills it mid-run — whatever
# sorts last never executes. Run the cheap, broad correctness surface
# first and the compile-heavy parity matrices last, so a timeout
# truncates the most expensive tail instead of the unit tests. Tiers are
# rough wall-cost buckets (measured warm-cache); unknown files default to
# mid-pack. Stable sort: in-file order (and fixture sharing) is preserved.
_BUDGET_TIER = {
    # ~0-15 s each: pure-host units + fast managed-plane gates
    "test_units": 0, "test_topology": 0, "test_config": 0,
    "test_wide_syscalls": 0, "test_seccomp": 0, "test_signals": 0,
    "test_multiproc": 0, "test_cli": 0, "test_procs_e2e": 0,
    # tens of seconds: single-engine device tiers
    "test_checkpoint": 1, "test_engine_phold": 1, "test_faults": 1,
    "test_observability": 2, "test_net_stack": 2, "test_bridge": 2,
    "test_sim_build": 3, "test_spill": 3, "test_optimistic": 3,
    "test_audit": 3, "test_resilience": 3, "test_analysis": 3,
    # the pressure chaos matrix is an acceptance gate: before the
    # compile-heavy parity matrices, like test_serve
    "test_pressure": 3,
    # the serve chaos choreography is an acceptance gate: it must land
    # BEFORE the compile-heavy parity matrices so a budget truncation
    # never silently skips it
    "test_serve": 3,
    # the async-sync chain-equality matrix is the ISSUE 10 acceptance
    # gate: same rule — ahead of the compile-heavy tier-4 matrices
    "test_async_sync": 3,
    # the self-balancing acceptance gate (ISSUE 11): same rule
    "test_balancer": 3,
    # the pipelined-handoff chain-equality matrix (ISSUE 15): same rule —
    # ahead of the compile-heavy tier-4 matrices
    "test_pipeline": 3,
    # the multi-worker host-plane chain-equality matrix (ISSUE 17):
    # same rule — ahead of the compile-heavy tier-4 matrices
    "test_hostplane": 3,
    # the per-interface scheduling-plane acceptance gate (ISSUE 19):
    # same rule — compat goldens + PIFO/Eiffel parity before the tail
    "test_qdisc": 3,
    # the profiling-plane acceptance gate (ISSUE 20): mostly pure-host
    # units plus one tiny islands run — cheap, keep it ahead of the tail
    "test_prof": 2,
    # the multi-chip mesh acceptance gate (ISSUE 12): same rule — its
    # shard_map cells compile more than the vmap tiers but the chain
    # matrix + relayout resume must land before the tier-4 tail
    "test_mesh": 3,
    # the elastic-resilience acceptance gate (ISSUE 13): same rule —
    # the kill_chip chaos matrix must land before the tier-4 tail
    "test_mesh_resilience": 3,
    # minutes: multi-engine parity matrices / many-shape compiles
    "test_gearbox": 4, "test_islands": 4, "test_rebalance": 4,
    "test_sharding": 4, "test_tcp": 4, "test_fleet": 4, "test_tgen": 5,
    # slow-marked e2e tiers (excluded from tier-1 anyway)
    "test_bridge_tcp": 6, "test_relay_e2e": 6,
}


def pytest_collection_modifyitems(session, config, items):
    items.sort(key=lambda it: _BUDGET_TIER.get(it.module.__name__, 3))


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def apps(tmp_path_factory):
    """Compile the tiny C workload programs once per session."""
    out = tmp_path_factory.mktemp("apps")
    cc = shutil.which("cc") or shutil.which("gcc")
    if cc is None:
        pytest.skip("no C compiler available")
    bins = {}
    for src in APPS_SRC.glob("*.c"):
        exe = out / src.stem
        # -lpthread must be explicit: this toolchain's libc does not fold
        # libpthread in, and a missing symbol here used to error out the
        # session fixture — killing EVERY managed-plane test at once
        subprocess.run(
            [cc, "-O1", "-o", str(exe), str(src), "-lpthread"], check=True,
            capture_output=True,
        )
        bins[src.stem] = str(exe)
    return bins
