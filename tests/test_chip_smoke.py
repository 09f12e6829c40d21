"""chip_smoke.py rehearsed on the CPU (ISSUE 21).

The script drives the device network stack on a TPU; here its phase
functions run at tiny sizes on the 8-device virtual CPU mesh, so a wrong
path, argument or control flow is found before chip time. Also pinned
here: the script refuses to run without a TPU, a compile cache placed
from outside is honoured and never swept, and nothing falls back to the
CPU unless a loss policy asks for it.
"""

import importlib.util
import json
import os

import jax
import pytest

from shadow_tpu.core.config import FaultOptions
from shadow_tpu.core.supervisor import BackendSupervisor
from shadow_tpu.procs import build as build_mod
from shadow_tpu.serve import kcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()

# tiny pools: the full-size shapes compile for minutes on the CPU
TINY = {"event_capacity": 2048}


def test_phase_a_flood_tiny():
    clock = cs.CompileClock()
    r = cs.phase_flood(64, stop_s=2, clock=clock, **TINY)
    assert r["events_committed"] > 0 and r["packets_delivered"] > 0
    assert r["pool_overflow_dropped"] == 0
    assert r["last_window_end_ns"] >= r["stop_ns"]
    assert r["compile_s"] > 0  # JAX's own compile events were summed


def test_phase_b_phold_tiny():
    r = cs.phase_phold(64, msgload=2, stop_s=2)
    assert r["events_committed"] > 64 * 2
    assert r["drops"] == {}


PREFIX = 1_500_000_000


def test_phase_c_chain_equality_cpu_against_cpu():
    """Phase C's comparison, with two CPU devices as chip and reference."""
    from functools import partial

    d = jax.devices()
    with jax.default_device(d[0]):
        chip = {
            "flood": cs.phase_flood(64, stop_s=2, prefix_ns=PREFIX, **TINY),
            "phold": cs.phase_phold(64, msgload=2, stop_s=2,
                                    prefix_ns=PREFIX),
        }
    ref = cs.reference_runs({
        "flood": (partial(cs.flood_sim, 64, 2, **TINY), cs.FLOOD_WPD),
        "phold": (partial(cs.phold_sim, 64, 2, 2), cs.PHOLD_WPD),
    }, PREFIX, reference=d[1])
    out = cs.phase_compare(chip, ref)
    for name in ("flood", "phold"):
        a, r = out[name]["chip"], out[name]["reference"]
        assert a["chain"] == r["chain"] != 0
        assert a["events_committed"] == r["events_committed"] > 0


def test_phase_c_detects_a_different_run():
    """The comparison fails when the reference runs something else (here:
    another seed), so an equal chain is evidence."""
    from shadow_tpu import flagship
    from shadow_tpu.sim import build_simulation

    d = jax.devices()
    with jax.default_device(d[0]):
        chip = cs.phase_flood(64, stop_s=2, prefix_ns=PREFIX, **TINY)

    def other_seed():
        cfg = flagship.udp_flood_config(64, stop_s=2, **TINY)
        cfg["general"]["seed"] += 1
        return build_simulation(cfg)

    ref = cs.reference_runs({"flood": (other_seed, cs.FLOOD_WPD)}, PREFIX,
                            reference=d[1])
    with pytest.raises(cs.PhaseFailed, match="flood: chain"):
        cs.phase_compare({"flood": chip}, ref)


@pytest.mark.skipif(not build_mod.toolchain_available(),
                    reason="no native toolchain")
def test_phase_d_managed_echo_through_device_network(tmp_path):
    r = cs.phase_managed(str(tmp_path))
    assert r["rtt_ns"] == [2 * cs.ECHO_LATENCY_MS * 1_000_000] * cs.ECHO_COUNT


def test_four_chip_phases_on_virtual_devices():
    """The --four-chips path: shard_map islands over 4 of the virtual
    devices, and its prefix chain against vmap islands on another device
    (the CPU reference of the real run)."""
    from functools import partial

    r = cs.phase_mesh(256, stop_s=2, prefix_ns=1_200_000_000, **TINY)
    assert len(r["bytes_in_use"]) == len(jax.devices())
    ref = cs.reference_runs({"mesh": (
        partial(cs.mesh_sim, "vmap", 256, 2, **TINY), cs.FLOOD_WPD,
    )}, 1_200_000_000, reference=jax.devices()[5])
    v = cs.phase_compare({"mesh": r}, ref)["mesh"]
    assert v["chip"]["chain"] == v["reference"]["chain"] != 0


def test_main_refuses_without_tpu(capsys):
    rc = cs.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert "needs a TPU" in out.err
    assert '"ok"' not in out.out  # no result line


def test_compile_cache_placed_from_outside_is_honoured_and_kept(
        tmp_path, monkeypatch):
    placed = tmp_path / "placed"
    placed.mkdir()
    # a foreign machine marker and entries JAX wrote there: the repo's
    # own sweep would delete them all
    (placed / "machine.json").write_text(json.dumps({"machine": "other"}))
    (placed / "jit_step-abc-cache").write_bytes(b"entry")
    (placed / "empty-entry").write_bytes(b"")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    monkeypatch.setenv("SHADOW_TPU_CACHE_DIR", str(tmp_path / "other"))
    prev = jax.config.jax_compilation_cache_dir
    try:
        root, evicted = kcache.enable_xla_cache(str(tmp_path / "explicit"))
        assert root == str(placed) and evicted == 0
        assert jax.config.jax_compilation_cache_dir == str(placed)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert sorted(os.listdir(placed)) == [
        "empty-entry", "jit_step-abc-cache", "machine.json"]
    assert not (tmp_path / "explicit").exists()
    assert not (tmp_path / "other").exists()


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("SHADOW_TPU_CACHE_DIR", raising=False)
    assert kcache.cache_root() == os.path.join(REPO, ".jax_cache")


def test_no_cpu_failover_unless_asked():
    """CPU failover arms only under an explicit `cpu` loss policy."""
    assert FaultOptions().on_backend_loss is None
    assert BackendSupervisor().policy == "abort"
    assert FaultOptions.from_dict(
        {"on_backend_loss": "cpu"}).on_backend_loss == "cpu"


@pytest.mark.parametrize("op", ["pmin", "pmax"])
def test_int64_collectives_exact_under_vmap(op):
    """The mesh kernel's int64 pmin/pmax run as two int32 all-reduces
    (TPU lowers a 64-bit all-reduce only as a sum); the split must give
    the exact 64-bit result, sign, NEVER and shared high words included."""
    import numpy as np

    from shadow_tpu.core import simtime
    from shadow_tpu.parallel import islands

    vals = np.array([
        [5, -3, int(simtime.NEVER), 7],
        [(1 << 32) + 1, (1 << 32) + 0xFFFFFFFF, (1 << 32), -(1 << 40)],
        [0xFFFFFFFF, 0x80000000, 0x7FFFFFFF, 1 << 33],
    ], dtype=np.int64)
    fn = jax.vmap(lambda x: getattr(islands, op)(x, "s"), axis_name="s")
    for row in vals:
        got = np.asarray(fn(jax.numpy.asarray(row)))
        want = row.min() if op == "pmin" else row.max()
        assert (got == want).all(), (row, got, want)
