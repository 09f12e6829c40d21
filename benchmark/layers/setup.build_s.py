"""Seconds `build_simulation` takes (builders: sim.py, net/stack.NetStack),
host clock; moves setup_s."""


def read(run):
    return run.build_s
