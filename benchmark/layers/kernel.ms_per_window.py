"""Device milliseconds of the window kernel per simulated window: busy
time of whole `run_to` executions in the trace over the windows a
dispatch runs (counter windows_run); moves sim_s_per_wall_s."""

from benchmark import window_kernel


def read(run):
    s = window_kernel.seconds_per_dispatch(run)
    w = window_kernel.per_dispatch(run, "windows")
    if not s or not w:
        return None
    return 1e3 * s / w
