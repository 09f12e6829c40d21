"""Share of the traced span in which no operation ran on the device:
1 - (union of device-op intervals) / span, from the profiler trace;
moves sim_s_per_wall_s."""


def read(run):
    if not run.trace or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
