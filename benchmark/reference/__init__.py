"""Plain references, one per app model, that import nothing of the program
under test.

Everything the benchmark knows of an app model is here, in
`reference/<app model>.py`, which `Spec.reference` loads by its file
path. The harness (run.py, compare.py, control.py) and its tests read
only this protocol and BENCHMARK.json, so a later app model is new files:

    FIELDS     {name: dotted state path}: per-host numbers the reference
               predicts ([H], or [H, slots] compared as host sums)
    TOTALS     {name: dotted state path}: whole-run counters it predicts
    CAPACITY   tuple of dotted state paths, () where there are none:
               counters of what the app model's bounded rings dropped for
               want of room, beyond the engine's pool and outbox drops;
               each is summed over hosts, and its growth over the window
               counts in `failed` (and so in `attempted`)
    expected(cfg, frontier_ns) -> dict
               what the program must hold once every event before
               `frontier_ns` is handled and none at or after it: each
               FIELDS and TOTALS name, and `pending` ([H] events waiting)
    control(cfg, frontier_ns) -> dict
               the same prediction made the way a later change might be
               tempted to make it, a step below the program's stated
               precision or guarantee; the comparison must fail it
               (control.py), so it is shown to tell the two apart

`common.py` holds what every reference reads from a configuration. A
reference takes it by path too, `spec.load_module(Path(__file__)
.with_name("common.py"))`, so that a copied tree's `common.py` is the one
that runs.
"""
