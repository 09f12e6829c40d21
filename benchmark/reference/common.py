"""What every plain reference reads from a Shadow configuration.

Written from the configuration format's own rules (Shadow's
`shadow_config_spec`): a host block with `quantity` N expands to hosts
named name1..nameN, hosts are numbered in name order, times are
"<number> <unit>" (a bare number is seconds), bandwidths are SI bits. The
references import nothing of the program under test.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

# int64 nanosecond clocks, and a seed wider than 32 bits keyed whole
jax.config.update("jax_enable_x64", True)

NS = {"ns": 1, "us": 10**3, "ms": 10**6, "s": 10**9, "min": 60 * 10**9,
      "h": 3600 * 10**9}
SI_BITS = {"bit": 1, "Kbit": 10**3, "Mbit": 10**6, "Gbit": 10**9,
           "Kibit": 2**10, "Mibit": 2**20, "Gibit": 2**30}


def time_ns(value) -> int:
    if isinstance(value, (int, float)):
        return int(round(value * NS["s"]))
    num, unit = re.fullmatch(r"\s*([0-9.]+)\s*([a-z]*)\s*", value).groups()
    return int(round(float(num) * NS[unit or "s"]))


def bits(value: str) -> int:
    num, unit = re.fullmatch(r"\s*([0-9.]+)\s*([A-Za-z]+)\s*", value).groups()
    return int(round(float(num) * SI_BITS[unit]))


def self_loop(cfg: dict) -> dict:
    """The one vertex and its self-loop edge of an inline GML graph."""
    gml = cfg["network"]["graph"]["inline"]
    node = re.search(r"node\s*\[(.*?)\]", gml, re.S).group(1)
    edge = re.search(r"edge\s*\[(.*?)\]", gml, re.S).group(1)
    if len(re.findall(r"node\s*\[", gml)) != 1:
        raise ValueError("the reference models a one-vertex graph only")
    loss = re.search(r"packet_loss\s+([0-9.eE+-]+)", edge)
    return {
        "latency_ns": time_ns(re.search(r'latency\s+"([^"]+)"', edge)
                              .group(1)),
        "loss": float(loss.group(1)) if loss else 0.0,
        "bw_up": bits(re.search(r'bandwidth_up\s+"([^"]+)"', node).group(1)),
        "bw_down": bits(re.search(r'bandwidth_down\s+"([^"]+)"', node)
                        .group(1)),
    }


def hosts(cfg: dict) -> list[tuple[str, dict]]:
    """(name, host block) per host, in host-number order."""
    out = []
    for name, block in cfg["hosts"].items():
        n = int(block.get("quantity", 1))
        names = [name] if n <= 1 else [f"{name}{i}" for i in range(1, n + 1)]
        out += [(nm, block) for nm in names]
    return sorted(out, key=lambda x: x[0])


def root_key(seed: int):
    """The experiment's key, from its whole seed."""
    return jax.random.PRNGKey(seed)


def host_keys_of(root, hosts):
    """Per-host streams: fold each host number into the experiment key."""
    return jax.vmap(lambda i: jax.random.fold_in(root, i))(
        hosts.astype(jnp.uint32))


def draw(keys, counters, dtype=jnp.float32):
    """The uniform draw a host's stream gives at each counter, elementwise
    over `keys` and `counters`. The program draws float32; the control
    (control.py) draws the next precision below."""
    return jax.vmap(lambda k, c: jax.random.uniform(
        jax.random.fold_in(k, c), dtype=dtype))(keys, counters)


def as_numpy(d: dict) -> dict:
    return {k: np.asarray(v) for k, v in d.items()}
