#!/usr/bin/env python3
"""Readings of the controls: the reference put in the program's place
with one stated guarantee broken, compared by the cell's own check.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--steps 3]

Each control has to come out as not correct: at least one of the
numbers the cell compares must read above its limit.  The cell's driver
(`drivers/<driver>.py`) draws the seed's graph and traffic as a run
does, its `control` fills the driver's window results with the
control's answers for `--steps` window steps, and the driver's own
`check` decides, as it does after a run.  The controls are plain numpy
and touch no chip, but each is read at the cell's own size.  One JSON
line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_control(workload: str, seed: int, *, steps: int = 3,
                 overrides=None, **options) -> dict:
    """The numbers of `workload`'s check for its control at this seed."""
    from bench import common, harness
    _, config, traffic = harness.load_cell(harness.load_spec(), workload)
    config, traffic = harness.scaled(config, traffic, overrides)
    d = common.make_driver(config, traffic, seed)
    common.load_module("drivers", traffic["driver"]).control(
        d, steps, **options)
    checks, failed = d.check()
    return {"workload": workload, "seed": seed, "steps": steps,
            "correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "failed": failed, "attempted": d.attempted(), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--steps", type=int, default=3,
                    help="window steps the control stands in for")
    args = ap.parse_args(argv)
    # the controls are numpy; the program's query types import jax,
    # which must not take the chip from a run beside them
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = read_control(args.workload, seed, steps=args.steps)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
