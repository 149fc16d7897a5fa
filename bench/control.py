"""The control: the reference in the program's place, in float32.

The deployments state float64 distances; float32 is the step below, the
one a later change could be tempted to take.  A run whose timed path is the
float32 reference has to come out not correct, and the size of its
``bars_off`` is the upper reading that the limit of 0 sits under.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

runs the whole harness (set-up, window, check) once per seed in one process,
with the float32 reference answering every call, and prints one
JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import spec    # noqa: E402


def control_driver(cell, seed: int, dtype=np.float32):
    """The cell's loop, driven with the reference in ``dtype`` answering in
    the program's place (``control`` of ``bench/loops/<loop>.py``)."""
    return spec.load_part(cell.root, "loops", cell.mix["loop"]).control(
        cell, seed, dtype)


def main(argv=None) -> int:
    from bench import run

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True,
                        help="comma-separated seeds")
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    cell = spec.load(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = run.run(cell, seed, args.seconds, traced=False,
                      driver=control_driver(cell, seed))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "float32 reference",
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
