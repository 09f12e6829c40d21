"""Force a virtual n-device CPU platform.

The tests, the CPU smoke gates and __graft_entry__.dryrun_multichip run
the sharding layer on a virtual CPU mesh
(``--xla_force_host_platform_device_count``) rather than on chips. The
jax config value is set along with JAX_PLATFORMS, so the force holds even
where jax was imported before the environment was changed.
"""

from __future__ import annotations

import os


def force_cpu_devices(n_devices: int):
    """Virtualize n CPU devices; must run before the JAX backend
    initializes (importing jax is fine — first device use is not). JAX's
    persistent compilation cache is turned on at the shared root
    (serve/kcache.enable_xla_cache: $JAX_COMPILATION_CACHE_DIR when set,
    else the repo default).

    Returns the jax module. Raises RuntimeError if virtualization did not
    take (e.g. a backend was already initialized on another platform).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    from shadow_tpu.serve.kcache import enable_xla_cache

    enable_xla_cache()

    ndev = len(jax.devices())
    if ndev < n_devices:
        raise RuntimeError(
            f"virtualization failed: need {n_devices} devices, have {ndev}"
        )
    return jax
