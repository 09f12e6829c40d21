"""The one traffic generator: a mix is data, read here.

A mix file (traffic/<config>.<mix>.json) holds

    why                    one line: what the mix exercises
    config                 a partial Shadow configuration deep-merged into
                           the cell's configuration: app options (message
                           load, send interval, datagram size, runtime),
                           stop_time
    warm_until             simulated time the set-up runs to before the
                           window; past the traffic's start and through at
                           least one full dispatch of traffic
    windows_per_dispatch   windows per device dispatch of the driver

The seed of the run becomes the configuration's `general.seed`: it draws
every host's random stream (destinations, loss rolls), so the same seed
gives the same traffic, and every seed the same sizes and rates.
"""

from __future__ import annotations

import copy

from benchmark.reference import common


def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def apply(config: dict, mix: dict, seed: int) -> dict:
    """The configuration as the run builds it."""
    cfg = merge(config, mix.get("config", {}))
    cfg.setdefault("general", {})["seed"] = int(seed)
    return cfg


def warm_until_ns(mix: dict) -> int:
    return common.time_ns(mix["warm_until"])


def windows_per_dispatch(mix: dict) -> int:
    return int(mix["windows_per_dispatch"])
