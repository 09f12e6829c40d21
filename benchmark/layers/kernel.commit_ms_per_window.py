"""Device milliseconds per simulated window of the window kernel's
`win.commit` phase: self time of the ops under that named scope in whole
`run_to` executions (scopes.py) over the windows a dispatch runs; moves
sim_s_per_wall_s."""

from benchmark import scopes


def read(run):
    return scopes.ms_per_window(run, "win.commit")
