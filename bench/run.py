#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are read from
`BENCHMARK.json` and the files under `bench/`.  The run refuses any
platform but a TPU with as many chips as the cell asks for: it exits
non-zero and prints no result.  Set-up (graph generation from the seed,
the program's state, warm-up of every shape the window uses) is timed
as `setup_s`; then the window runs for `--seconds` and ends at the first
completed unit of work after that.  With `--trace 1` the window runs
under the profiler and the per-layer metrics are printed instead of the
end-to-end ones.  After the window the program's state is freed and the
window's results are compared with the plain reference in
`bench/reference.py`: the numbers compared and their limits are the last
lines on standard error and the `checks` key of the result line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import harness
    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)
    device = harness.device_info(int(cell["chips"]))
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as exc:
        sys.exit(f"bench: the program is not under {ROOT}/src ({exc})")
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START, spec=spec,
                         device=device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
