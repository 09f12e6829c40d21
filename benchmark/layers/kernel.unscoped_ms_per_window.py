"""Device milliseconds per simulated window of whole `run_to` executions
under no `win.*` phase: the fused loop's own control (its `while` and
`conditional`, the copies of its carried state, the occupancy sum)
(scopes.py); with the four phases it adds up to kernel.ms_per_window;
moves sim_s_per_wall_s."""

from benchmark import scopes


def read(run):
    return scopes.ms_per_window(run, scopes.UNSCOPED)
