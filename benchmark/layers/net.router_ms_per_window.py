"""Device milliseconds per simulated window of the router rings and CoDel
(net/codel.py): self time of the ops under the `net.router` named scope
in whole `run_to` executions (net_scopes.py) over the windows a dispatch
runs; moves sim_s_per_wall_s."""

from benchmark import net_scopes


def read(run):
    return net_scopes.ms_per_window(run, "net.router")
