"""The window kernel's share of its memory roofline: committed events per
dispatch x bytes per committed event (tables/event_bytes/<config>.json,
frozen per configuration) / peak HBM bandwidth (tables/peaks.json) / the
kernel's device seconds per dispatch; moves sim_s_per_wall_s."""

from benchmark import window_kernel


def read(run):
    s = window_kernel.seconds_per_dispatch(run)
    ev = window_kernel.per_dispatch(run, "events")
    if not s or not ev:
        return None
    try:
        per_event = run.spec.table(f"event_bytes/{run.cell['config']}")
    except FileNotFoundError:  # no row size frozen for this configuration
        return None
    peak = run.spec.table("peaks")[run.device_kind]["hbm_bytes_per_s"]
    return 100.0 * ev * per_event["bytes_per_event"] / peak / s
