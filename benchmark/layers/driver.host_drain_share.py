"""Share of the traced span's wall time the driver (Simulation.run) spends
in its `host_drain` span; moves sim_s_per_wall_s."""


def read(run):
    if len(run.traced) < 2:
        return None
    lo, hi = run.traced[0].t, run.traced[-1].t
    drain = sum(min(e, hi) - max(s, lo) for name, s, e in run.spans
                if name == "host_drain" and min(e, hi) > max(s, lo))
    return 100.0 * drain / (hi - lo)
