"""The control of the comparison: the plain reference's `control`, its
prediction made a step below the program's stated precision or guarantee
(PHOLD: its random draws in bfloat16, below the float32 the program
draws in), put in the program's place. It must come out as not correct,
so the comparison is shown to tell a different run from a sound one. The
benchmark's own runs do not run it.

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3 --frontier-s 60

prints, per seed, each compared number of the control and of the
reference against itself at the frontier: a run's frontier is where its
window ended (`--frontier-s` simulated seconds).
"""

from __future__ import annotations

import argparse
import json

from benchmark import compare, spec as spec_mod, traffic
from benchmark.run import _app_model


def readings(cell_name: str, seed: int, frontier_ns: int,
             spec=None) -> dict:
    spec = spec or spec_mod.Spec.load()
    cell = spec.cell(cell_name)
    cfg = traffic.apply(spec.config(cell), spec.traffic(cell), seed)
    ref = spec.reference(_app_model(cfg))
    sound = ref.expected(cfg, frontier_ns)
    control = ref.control(cfg, frontier_ns)
    return {"seed": seed, "frontier_ns": frontier_ns,
            "control": compare.compare(control, sound, ref),
            "sound": compare.compare(sound, sound, ref)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--frontier-s", type=float, required=True)
    args = ap.parse_args(argv)
    import jax

    print(f"# {jax.devices()[0].device_kind}", flush=True)
    spec = spec_mod.Spec.load()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(args.workload, seed, int(args.frontier_s * 1e9),
                       spec)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
