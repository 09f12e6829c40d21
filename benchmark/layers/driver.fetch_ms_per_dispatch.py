"""Host milliseconds of the driver's `fetch` span per dispatch: the one
transfer of a finished `run_to` dispatch's frontier, pressure flag,
occupancy and committed counters to the host (core/engine.py
Simulation.run), after its `wait` span; moves sim_s_per_wall_s."""


def read(run):
    fetch = [e - s for name, s, e in run.spans if name == "fetch"]
    if not fetch:
        return None
    return 1e3 * sum(fetch) / len(fetch)
