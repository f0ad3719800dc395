"""Benchmark harness — one module per paper table/figure (see DESIGN §6).

Prints ``name,us_per_call,derived`` CSV and writes one machine-readable
``BENCH_<name>.json`` per benchmark at the repo root (so the perf
trajectory is trackable across PRs). ``--scale N`` grows the datasets.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.compat import use_compile_cache

from benchmarks import (bench_batch_updates, bench_block_sweep, bench_build,
                        bench_extremes, bench_maintenance, bench_scaling,
                        bench_serve, bench_sig_store, bench_stream)

ALL = [
    ("fig3_table7_build", bench_build.run, True),
    ("fig4_sig_store", bench_sig_store.run, True),
    ("fig5_block_sweep", bench_block_sweep.run, True),
    ("fig6_scaling", bench_scaling.run, False),
    ("fig7_8_maintenance", bench_maintenance.run, True),
    ("fig9_10_extremes", bench_extremes.run, False),
    ("fig11_batch_updates", bench_batch_updates.run, True),
    ("fig12_prefetch", bench_build.run_prefetch, True),
    ("serve", bench_serve.run, True),
    ("stream", bench_stream.run, True),
]


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_json(name: str, rows, scale: int, seconds: float,
               root: str = _REPO_ROOT, extras: dict = None) -> str:
    """Emit BENCH_<name>.json: {name, scale, seconds, rows:[{name,us,meta}]}.
    ``extras`` (e.g. a ``phases`` table from `repro.obs`) merges into the
    payload top level."""
    path = os.path.join(root, f"BENCH_{name}.json")
    payload = {
        "name": name,
        "scale": scale,
        "seconds": seconds,
        "rows": [{"name": rname, "us": round(float(us), 1), "meta": derived}
                 for rname, us, derived in rows],
    }
    payload.update(extras or {})
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark name")
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing BENCH_<name>.json files")
    args = ap.parse_args()
    use_compile_cache()

    print("name,us_per_call,derived")
    t_start = time.perf_counter()
    for name, fn, scalable in ALL:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        out = fn(scale=args.scale) if scalable else fn()
        dt = time.perf_counter() - t0
        # benchmarks may return (rows, extras) — extras (a "phases"
        # breakdown from repro.obs, typically) lands in the JSON payload
        rows, extras = out if isinstance(out, tuple) else (out, {})
        for rname, us, derived in rows:
            print(f"{name}/{rname},{us:.1f},{derived}")
        if not args.no_json:
            path = write_json(name, rows, args.scale, dt, extras=extras)
            print(f"# wrote {path}", file=sys.stderr)
    print(f"# total benchmark wall time: "
          f"{time.perf_counter() - t_start:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
