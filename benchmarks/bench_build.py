"""Paper Fig. 3 / Table 7: Build_Bisim per-iteration behavior (k=10).

Columns mirror Table 7: partition count, constructing time, bytes
sorted/scanned (the STXXL I/O analogue), per dataset per iteration.
The out-of-core engine runs on a subset of the suite with chunked
tables, reporting the measured `sort_cost`/`scan_cost` record counters
alongside wall time — the disk-resident Table-7 row.
"""
from __future__ import annotations

import tempfile
import time

from repro.core import build_bisim
from repro.exmem import build_bisim_oocore
from repro.obs import MetricsReport
from repro.obs import tracer as obs

from .datasets import suite


def run(scale: int = 1, k: int = 10):
    rows = []
    datasets = suite(scale)
    for name, g in datasets.items():
        # per-build tracer: the total row carries the dispatch/sync
        # counts the fused while_loop build contracts to (1 and 1)
        t = obs.Tracer()
        with obs.tracing(t):
            res = build_bisim(g, k, mode="sorted", early_stop=True)
        for st in res.stats:
            rows.append((
                f"build/{name}/iter{st.iteration}",
                st.seconds * 1e6,
                f"partitions={st.num_partitions};"
                f"bytes_sorted={st.bytes_sorted};"
                f"bytes_scanned={st.bytes_scanned};"
                f"nodes={g.num_nodes};edges={g.num_edges}"))
        rows.append((
            f"build/{name}/total", sum(s.seconds for s in res.stats) * 1e6,
            f"converged_at={res.converged_at};"
            f"final_partitions={res.counts[-1]};"
            f"partition_ratio={res.counts[-1] / g.num_nodes:.4f};"
            f"dispatches={len(t.find_events('build.dispatch'))};"
            f"sync_count={len(t.find('build.sync'))}"))
    # one tracer across the oocore rows: the BENCH payload gains a
    # "phases" breakdown (where the disk build's time actually goes)
    tracer = obs.Tracer()
    for name in ("jamendo-like", "sp2b-like"):
        g = datasets[name]
        with tempfile.TemporaryDirectory() as td:
            t0 = time.perf_counter()
            # chunk small enough that even jamendo-like (11k edges at
            # scale=1) is multi-chunk — the row must exercise the k-way
            # merge and windowed ranking, not the single-run fast path
            with obs.tracing(tracer):
                res = build_bisim_oocore(g, k, chunk_edges=2048,
                                         workdir=td)
            dt = time.perf_counter() - t0
            io = res.io
            rows.append((
                f"build/{name}/oocore_total", dt * 1e6,
                f"converged_at={res.converged_at};"
                f"final_partitions={res.counts[-1]};"
                f"sort_cost={io.sort_cost};scan_cost={io.scan_cost};"
                f"spills={io.spills};runs={io.runs_written}"))
    report = MetricsReport.from_tracer(tracer).as_dict()
    return rows, {"phases": report["phases"], "levels": report["levels"]}


def run_prefetch(scale: int = 1, k: int = 10, reps: int = 3):
    """Fig. 12 (ours): sync vs async-pipeline head-to-head.

    The identical chunked build (same dataset, same chunk geometry, so
    same runs / merges / IOStats) with the `exmem.aio` pipeline off
    (``io_threads=0``) and on (``io_threads=2``), at two chunk sizes on
    a multi-chunk powerlaw graph.  One untimed warmup per chunk size
    absorbs the jit compile of the per-chunk fold (its cache is keyed on
    chunk_edges); the two configs then run *interleaved* ``reps`` times
    and each row reports the min — machine noise hits both arms equally
    instead of whichever ran second."""
    from repro.graph import generators as gen

    rows = []
    g = gen.powerlaw_graph(100_000 * scale, 400_000 * scale, 4, 3, seed=0)
    # chunk sizes where the per-chunk device dispatch amortizes and the
    # streams are long enough that I/O scheduling is what's measured —
    # the regime the paper's overlap targets (4..13 chunks at scale=1)
    configs = (("sync", 0), ("prefetch", 2))
    for chunk in (65536, 131072):
        with tempfile.TemporaryDirectory() as td:
            build_bisim_oocore(g, k, chunk_edges=chunk, workdir=td,
                               io_threads=0)
        best = {}   # label -> (dt, res-derived meta)
        for _ in range(reps):
            for label, threads in configs:
                with tempfile.TemporaryDirectory() as td:
                    t0 = time.perf_counter()
                    res = build_bisim_oocore(g, k, chunk_edges=chunk,
                                             workdir=td,
                                             io_threads=threads,
                                             prefetch_depth=2)
                    dt = time.perf_counter() - t0
                    aio = res.aio.to_dict()
                    meta = (f"io_threads={threads};"
                            f"final_partitions={res.counts[-1]};"
                            f"sort_cost={res.io.sort_cost};"
                            f"read_wait_s={aio['read_wait_s']};"
                            f"write_wait_s={aio['write_wait_s']};"
                            f"prefetched={aio['chunks_prefetched']}")
                    if label not in best or dt < best[label][0]:
                        best[label] = (dt, meta)
        for label, _ in configs:
            dt, meta = best[label]
            rows.append((f"prefetch/powerlaw/chunk{chunk}/{label}",
                         dt * 1e6, meta))
    return rows
