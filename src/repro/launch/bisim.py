"""Bisimulation launcher: run Build_Bisim (single, distributed, or
out-of-core) on a generated or saved graph, or maintain the partition
under updates via the `add-edges` / `delete-node` / `compact`
subcommands (in-memory by default; with `--oocore`, through the
disk-resident `OocBackend`).

    PYTHONPATH=src python -m repro.launch.bisim --generator powerlaw \
        --nodes 100000 --edges 400000 --k 10 --mode sorted
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.bisim --distributed \
        --ranking bucketed --generator structured --nodes 50000
    PYTHONPATH=src python -m repro.launch.bisim --oocore \
        --chunk-edges 65536 --generator structured --nodes 300000
    PYTHONPATH=src python -m repro.launch.bisim --oocore \
        --chunk-edges 4096 --generator structured --nodes 9000 --k 5 \
        add-edges --count 16
    PYTHONPATH=src python -m repro.launch.bisim --oocore \
        --generator random --nodes 5000 --k 4 compact --delete-nodes 3,7,11

Quotient serving (repro.quotient): `materialize` persists the per-level
quotient graphs + extents, `query` answers structural queries over them
(optionally absorbing update batches live):

    PYTHONPATH=src python -m repro.launch.bisim --generator structured \
        --nodes 9000 --k 5 materialize --quotient-dir /tmp/q
    PYTHONPATH=src python -m repro.launch.bisim --generator structured \
        --nodes 9000 --k 5 query --path 0:1 --point 7 --update 8

Durability: `--checkpoint --workdir DIR` makes the oocore build write a
per-level checkpoint (add `--resume` to continue a killed build from the
last finished level); `--wal --workdir DIR` runs the maintenance
subcommands write-ahead-logged with a final snapshot, and the `recover`
subcommand re-opens such a workdir after a crash (snapshot + committed
WAL replay) and reports the recovered partition.
"""
from __future__ import annotations

import argparse
import time

from repro.compat import use_compile_cache
from repro.core import build_bisim, build_bisim_distributed
from repro.graph import generators as gen
from repro.graph.storage import Graph
from repro.obs import MetricsReport, write_chrome_trace
from repro.obs import tracer as obs


def make_graph(args) -> Graph:
    if args.graph:
        return Graph.load(args.graph)
    if args.generator == "random":
        return gen.random_graph(args.nodes, args.edges, 4, 3, seed=args.seed)
    if args.generator == "powerlaw":
        return gen.powerlaw_graph(args.nodes, args.edges, 4, 3,
                                  seed=args.seed)
    if args.generator == "structured":
        return gen.structured_graph(args.nodes // 3, seed=args.seed)
    if args.generator == "dag":
        return gen.random_dag(args.nodes, args.edges, 4, 3, seed=args.seed)
    if args.generator == "dbest":
        return gen.kary_tree(4, 9)
    if args.generator == "dworst":
        return gen.complete_graph(args.nodes)
    raise SystemExit(f"unknown generator {args.generator}")


# Global flags that apply to every subcommand but are declared on the
# top-level parser (argparse only shows them under the bare --help), so
# each subparser repeats them in its epilog — the parser-contract test
# in tests/test_launcher.py keeps this list and the flags in sync.
_SHARED_EPILOG = """\
shared flags (pass them BEFORE the subcommand):
  --trace PATH          write a Chrome-trace JSON of the whole run and
                        print the aggregated per-phase table
  --wal-group N         WAL group-commit size (records per fsync; used
                        with --wal --workdir)
  --sync-every N        force the STAGED single-device build, draining
                        convergence scalars every N iterations
  --device-maintenance  run update propagation on device (bit-identical
                        to the host path)
"""


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default=None, help="path to saved .npz graph")
    ap.add_argument("--generator", default="powerlaw")
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--edges", type=int, default=400_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--mode", default="sorted",
                    choices=["sorted", "dedup_hash", "multiset"])
    # one engine per session: the distributed builder has no out-of-core
    # tables (and no maintenance backend), so the flags cannot combine
    engine = ap.add_mutually_exclusive_group()
    engine.add_argument("--distributed", action="store_true")
    engine.add_argument("--oocore", action="store_true",
                        help="disk-resident streamed build (repro.exmem)")
    ap.add_argument("--ranking", default="allgather",
                    choices=["allgather", "bucketed"])
    ap.add_argument("--chunk-edges", type=int, default=1 << 16,
                    help="oocore: E_t chunk rows (memory budget)")
    ap.add_argument("--chunk-nodes", type=int, default=None,
                    help="oocore: N_t chunk rows (default: --chunk-edges)")
    ap.add_argument("--spill-threshold", type=int, default=1 << 20,
                    help="oocore: SigStore entries resident before spill")
    ap.add_argument("--workdir", default=None,
                    help="oocore: spill directory (default: a tempdir)")
    ap.add_argument("--io-threads", type=int, default=1,
                    help="oocore: async I/O pipeline threads (prefetch "
                         "readers / streaming writers / run saves); "
                         "0 = fully synchronous")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="oocore: chunks buffered ahead per stream")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="oocore: disable the async I/O pipeline "
                         "(same as --io-threads 0)")
    ap.add_argument("--checkpoint", action="store_true",
                    help="oocore build: write a per-level checkpoint to "
                         "--workdir (required)")
    ap.add_argument("--resume", action="store_true",
                    help="oocore build: resume a checkpointed build from "
                         "the last finished level (implies --checkpoint)")
    ap.add_argument("--wal", action="store_true",
                    help="oocore maintenance: write-ahead-log every "
                         "update and snapshot the backend afterwards "
                         "(requires --workdir)")
    ap.add_argument("--wal-group", type=int, default=1,
                    help="oocore maintenance: WAL group-commit size "
                         "(records per fsync; at most group-1 "
                         "acknowledged updates can be lost)")
    ap.add_argument("--device-maintenance", action="store_true",
                    help="maintenance subcommands: run the frontier "
                         "signature fold (and, in-memory, the store "
                         "resolve) on device — bit-identical to the host "
                         "path, reported per level")
    ap.add_argument("--no-early-stop", action="store_true")
    ap.add_argument("--sync-every", type=int, default=None, metavar="N",
                    help="force the STAGED single-device build, draining "
                         "convergence scalars every N iterations; default "
                         "is the fused while_loop build (one dispatch, "
                         "one sync — count them with --trace)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "to PATH and print the aggregated phase table "
                         "(works with every subcommand)")
    ap.add_argument("--out", default=None,
                    help="save pid history as .npz: one stacked 'pids' "
                         "array, or per-level 'pids_<j>' members with "
                         "--oocore (never materializes the full history)")
    sub = ap.add_subparsers(
        dest="cmd",
        metavar="{add-edges,delete-node,compact,recover,materialize,"
                "query,serve-updates}",
        help="subcommands: apply one update through BisimMaintainer "
             "(in-memory, or OocBackend with --oocore), recover a "
             "crashed workdir, materialize/query the quotient "
             "artifact (repro.quotient), or run the streaming "
             "maintenance service (repro.exmem.service)")

    def _sub(name, help):
        return sub.add_parser(
            name, help=help, epilog=_SHARED_EPILOG,
            formatter_class=argparse.RawDescriptionHelpFormatter)

    ap_add = _sub("add-edges", "insert edges and propagate (Alg. 4)")
    ap_add.add_argument("--count", type=int, default=1,
                        help="number of random edges to insert")
    ap_add.add_argument("--edge", action="append", default=[],
                        metavar="S:L:T",
                        help="explicit src:elabel:dst edge (repeatable; "
                             "overrides --count)")
    ap_del = _sub("delete-node", "DELETE_NODE: drop incident edges, "
                                 "tombstone the row")
    ap_del.add_argument("--nid", type=int, required=True)
    ap_cmp = _sub("compact", "drop tombstoned rows, remap ids densely")
    ap_cmp.add_argument("--delete-nodes", default="", metavar="I,J,...",
                        help="tombstone these nodes first")
    _sub("recover",
         "re-open a crashed --wal workdir: restore the last snapshot "
         "(checksum-verified) and replay the committed WAL tail")
    ap_mat = _sub("materialize",
                  "build the partition and persist the per-level "
                  "quotient graphs + extents (repro.quotient)")
    ap_mat.add_argument("--quotient-dir", required=True,
                        help="artifact directory (overwritten)")
    ap_qry = _sub("query",
                  "serve structural queries over the quotient: load an "
                  "existing --quotient-dir read-only, or build + "
                  "materialize first; --update streams maintenance "
                  "batches through the live service between queries")
    ap_qry.add_argument("--quotient-dir", default=None,
                        help="load this artifact read-only (no --update) "
                             "instead of building one")
    ap_qry.add_argument("--path", action="append", default=[],
                        metavar="L:L:...",
                        help="label-path query, colon-separated edge "
                             "labels (repeatable)")
    ap_qry.add_argument("--level", type=int, default=None,
                        help="quotient level to answer at (default: "
                             "path length)")
    ap_qry.add_argument("--point", action="append", default=[], type=int,
                        metavar="NID",
                        help="pId/block-size lookup for this node "
                             "(repeatable)")
    ap_qry.add_argument("--update", type=int, default=0, metavar="N",
                        help="apply N random edge inserts through the "
                             "live QuotientService, then re-query at "
                             "the new epoch")
    ap_qry.add_argument("--batch", type=int, default=64,
                        help="engine wave width (fixed slots per "
                             "dispatch)")
    ap_srv = _sub("serve-updates",
                  "streaming maintenance service: replay an open-loop "
                  "stream of mixed ops through the WAL'd ingest loop "
                  "(batched apply, compaction/snapshot cadence, live "
                  "quotient index within a staleness bound); requires "
                  "--oocore --wal --workdir")
    ap_srv.add_argument("--ops", type=int, default=200,
                        help="synthesized stream length (mixed "
                             "insert/delete/add-node ops)")
    ap_srv.add_argument("--rate", type=float, default=0.0,
                        help="arrival rate in ops/sec (0 = closed-loop, "
                             "as fast as the service absorbs)")
    ap_srv.add_argument("--batch-ops", type=int, default=32,
                        help="apply the pending batch at this many ops")
    ap_srv.add_argument("--batch-deadline-ms", type=float, default=50.0,
                        help="... or when the oldest pending op is this "
                             "old")
    ap_srv.add_argument("--snapshot-every", type=int, default=8,
                        help="snapshot cadence in applied batches "
                             "(0 = only the final close snapshot)")
    ap_srv.add_argument("--staleness-batches", type=int, default=1,
                        help="absorb the quotient index after this many "
                             "applied batches (the staleness bound)")
    ap_srv.add_argument("--compact-threshold", type=float, default=0.25,
                        help="tombstone fraction that schedules a WAL'd "
                             "compact op (0 disables; forced to 0 with "
                             "--kill-at-op for bit-identical recovery)")
    ap_srv.add_argument("--async-wal", action="store_true",
                        help="run WAL group-commit fsync rounds on the "
                             "aio executor (drained at snapshot/close)")
    ap_srv.add_argument("--no-quotient", action="store_true",
                        help="ingest + durability only: skip the live "
                             "quotient index")
    ap_srv.add_argument("--kill-at-op", type=int, default=0, metavar="N",
                        help="crash drill: abandon the service after N "
                             "submitted ops (no clean close), recover "
                             "from the snapshot + committed WAL, resubmit "
                             "the lost suffix, and verify the pid "
                             "history is bit-identical to an "
                             "uninterrupted reference run")
    return ap


def _io_threads(args) -> int:
    return 0 if args.no_prefetch else args.io_threads


def _report_overlap(aio_stats, compute_s: float) -> None:
    """One-line overlap report: how long the consumer waited on reads vs
    how long the fold/rank side ran (the paper's I/O-vs-compute split).
    Formatting lives in `MetricsReport.format_overlap` so every
    subcommand reports through the same code path."""
    line = MetricsReport.format_overlap(
        aio_stats.as_dict() if aio_stats is not None else None, compute_s)
    if line is not None:
        print(line)


def _report_update(rep, dt: float, m) -> None:
    import numpy as np
    if rep is not None:
        path = "device" if rep.device else "host"
        for j, (chk, chg, part, sec) in enumerate(zip(
                rep.nodes_checked, rep.nodes_changed,
                rep.partitions_touched, rep.level_seconds), start=1):
            print(f"  level {j:2d}: checked={chk} changed={chg} "
                  f"partitions_touched={part} "
                  f"{path}_ms={sec * 1e3:.2f}")
        if rep.rebuilt:
            print("  rebuilt (rebuild_threshold heuristic fired)")
    print(f"update: {dt * 1e3:.1f} ms; "
          f"partitions@k={len(np.unique(m.pid()))}")


def run_recover(args) -> None:
    """Re-open a crashed --wal workdir: verified snapshot + WAL replay."""
    import numpy as np

    from repro.core import BisimMaintainer
    from repro.exmem import OocBackend

    if not (args.oocore and args.workdir):
        raise SystemExit("recover needs --oocore and --workdir")
    t0 = time.perf_counter()
    backend, state = OocBackend.restore(
        args.workdir, io_threads=_io_threads(args),
        prefetch_depth=args.prefetch_depth)
    m = BisimMaintainer.restore(backend, state,
                                device=args.device_maintenance)
    dt = time.perf_counter() - t0
    print(f"recovered: k={m.k} mode={m.mode} "
          f"nodes={backend.num_nodes} tombstones={m.num_tombstones} "
          f"wal_lsn={state['wal_lsn']} in {dt:.2f}s")
    print(MetricsReport.format_io(
        backend.io.as_dict(), label="recovery io",
        fields=["sort_cost", "scan_cost", "sort_bytes", "scan_bytes"]))
    _report_overlap(backend.aio.stats, dt)
    print(f"partitions@k={len(np.unique(m.pid()))}")
    print(f"workdir: {backend.workdir}")


def run_maintenance(args, g: Graph) -> None:
    import numpy as np

    from repro.core import BisimMaintainer

    if args.distributed:
        raise SystemExit(
            "maintenance subcommands support the single and --oocore "
            "engines (the distributed builder keeps no store)")
    if args.wal and not (args.oocore and args.workdir):
        raise SystemExit("--wal needs --oocore and --workdir (a tempdir "
                         "workdir would be deleted on exit, defeating "
                         "the point of durability)")
    t0 = time.perf_counter()
    if args.oocore:
        from repro.exmem import OocBackend
        backend = OocBackend(
            g, chunk_edges=args.chunk_edges, chunk_nodes=args.chunk_nodes,
            spill_threshold=args.spill_threshold, workdir=args.workdir,
            io_threads=_io_threads(args), prefetch_depth=args.prefetch_depth,
            wal=args.wal, wal_group=args.wal_group)
        m = BisimMaintainer(backend, args.k, mode=args.mode,
                            device=args.device_maintenance, wal=args.wal)
    else:
        backend = None
        m = BisimMaintainer(g, args.k, mode=args.mode,
                            device=args.device_maintenance)
    engine = "oocore" if args.oocore else "in-memory"
    prop = "device" if m.device else "host"
    print(f"initial build ({engine}, k={args.k}, mode={args.mode}, "
          f"propagation={prop}): {time.perf_counter() - t0:.2f}s")
    io0 = backend.io.to_dict() if backend is not None else None

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.cmd == "add-edges":
        if args.edge:
            triples = [tuple(int(x) for x in e.split(":"))
                       for e in args.edge]
            src, lab, dst = (np.array(c, dtype=np.int32)
                             for c in zip(*triples))
        else:
            n = m.backend.num_nodes
            src = rng.integers(0, n, args.count).astype(np.int32)
            dst = rng.integers(0, n, args.count).astype(np.int32)
            lab = rng.integers(0, 4, args.count).astype(np.int32)
        rep = m.add_edges(src, lab, dst)
        print(f"add-edges: {src.shape[0]} edges")
    elif args.cmd == "delete-node":
        rep = m.delete_node(args.nid)
        print(f"delete-node {args.nid}: tombstones={m.num_tombstones}")
    else:  # compact
        rep = None
        victims = [int(x) for x in args.delete_nodes.split(",") if x]
        for nid in victims:
            m.delete_node(nid)
        remap = m.compact()
        print(f"compact: dropped {int((remap < 0).sum())} rows -> "
              f"{m.backend.num_nodes} nodes, {m.backend.num_edges} edges")
    dt = time.perf_counter() - t0
    _report_update(rep, dt, m)
    if args.wal:
        t0 = time.perf_counter()
        with obs.span("launch.snapshot"):
            m.snapshot()
        print(f"snapshot: {time.perf_counter() - t0:.2f}s "
              f"(wal truncated to lsn {backend._wal.committed_lsn})")
    if backend is not None:
        io1 = backend.io.to_dict()
        delta = {key: io1[key] - io0[key] for key in io1}
        print(MetricsReport.format_io(
            delta, label="io delta",
            fields=["sort_cost", "scan_cost", "sort_bytes", "scan_bytes",
                    "merge_passes", "spills"]))
        _report_overlap(backend.aio.stats, dt)
        if args.workdir:
            print(f"workdir: {backend.workdir}")
        else:
            backend.close()


def _make_maintainer(args, g: Graph):
    """Build a `BisimMaintainer` from the engine flags (shared by the
    maintenance and quotient subcommands)."""
    from repro.core import BisimMaintainer

    if args.distributed:
        raise SystemExit(
            "this subcommand supports the single and --oocore engines "
            "(the distributed builder keeps no store)")
    if args.oocore:
        from repro.exmem import OocBackend
        backend = OocBackend(
            g, chunk_edges=args.chunk_edges, chunk_nodes=args.chunk_nodes,
            spill_threshold=args.spill_threshold, workdir=args.workdir,
            io_threads=_io_threads(args), prefetch_depth=args.prefetch_depth,
            wal=args.wal, wal_group=args.wal_group)
        return BisimMaintainer(backend, args.k, mode=args.mode,
                               device=args.device_maintenance,
                               wal=args.wal), backend
    return BisimMaintainer(g, args.k, mode=args.mode,
                           device=args.device_maintenance), None


def run_materialize(args, g: Graph) -> None:
    from repro.exmem.runs import IOStats
    from repro.quotient import materialize_quotient

    t0 = time.perf_counter()
    m, backend = _make_maintainer(args, g)
    print(f"initial build: {time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    io = IOStats()
    index = materialize_quotient(
        backend.ooc if backend is not None else g, m.backend,
        args.quotient_dir, counts=[int(x) for x in m.next_pid],
        mode=m.mode, stats=io, overwrite=True)
    dt = time.perf_counter() - t0
    for j in range(1, index.k + 1):
        print(f"  Q_{j}: {index.counts[j]} blocks, "
              f"{index.levels[j].num_edges} edges")
    print(MetricsReport.format_io(
        io.as_dict(), label="materialize io",
        fields=["sort_cost", "scan_cost", "sort_bytes", "scan_bytes"]))
    print(f"materialized {args.quotient_dir} in {dt:.2f}s "
          f"(k={index.k}, mode={index.mode}, epoch={index.epoch})")
    if backend is not None and not args.workdir:
        backend.close()


def run_query(args) -> None:
    import os

    import numpy as np

    from repro.quotient import (LabelPath, PointLookup, QuotientEngine,
                                QuotientIndex, QuotientService)

    paths = [tuple(int(x) for x in p.split(":")) for p in args.path]
    svc = None
    if args.quotient_dir and os.path.exists(
            os.path.join(args.quotient_dir, "manifest.json")):
        if args.update:
            raise SystemExit("--update needs a live service; drop "
                             "--quotient-dir to build one")
        index = QuotientIndex.load(args.quotient_dir, verify=True)
        engine = QuotientEngine(index, max_batch=args.batch)
        print(f"loaded {args.quotient_dir}: k={index.k} "
              f"mode={index.mode} epoch={index.epoch}")
    else:
        g = make_graph(args)
        print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges")
        t0 = time.perf_counter()
        m, backend = _make_maintainer(args, g)
        import tempfile
        workdir = args.workdir or tempfile.mkdtemp(prefix="quotient-")
        svc = QuotientService(m, workdir, max_batch=args.batch)
        engine, index = svc.engine, svc.index
        print(f"build + materialize: {time.perf_counter() - t0:.2f}s "
              f"(epoch {svc.epoch})")

    queries = [LabelPath(p, level=args.level) for p in paths]
    queries += [PointLookup(nid, index.k) for nid in args.point]
    if not queries:
        queries = [PointLookup(0, index.k)]

    def _report(answers):
        for q, a in zip(queries, answers):
            if isinstance(q, PointLookup):
                print(f"  point {q.node}@{q.level}: pid={a.pid} "
                      f"block_size={a.block_size}")
            else:
                head = ",".join(str(x) for x in a[:8])
                more = "..." if a.shape[0] > 8 else ""
                print(f"  path {q.labels}: {a.shape[0]} nodes "
                      f"[{head}{more}]")

    t0 = time.perf_counter()
    answers = engine.query(queries)
    print(f"epoch {engine.epoch}: {len(queries)} queries "
          f"in {(time.perf_counter() - t0) * 1e3:.1f} ms "
          f"({engine.stats['waves']} waves, {engine.stats['hops']} hops)")
    _report(answers)
    if args.update and svc is not None:
        rng = np.random.default_rng(args.seed)
        n = svc.m.backend.num_nodes
        src = rng.integers(0, n, args.update).astype(np.int32)
        dst = rng.integers(0, n, args.update).astype(np.int32)
        lab = rng.integers(0, 4, args.update).astype(np.int32)
        t0 = time.perf_counter()
        svc.add_edges(src, lab, dst)
        print(f"absorbed {args.update} edge inserts in "
              f"{(time.perf_counter() - t0) * 1e3:.1f} ms "
              f"(patches={svc.patches}, "
              f"rematerializations={svc.rematerializations})")
        answers = svc.query(queries)
        print(f"epoch {svc.engine.epoch}:")
        _report(answers)


def run_serve(args, g: Graph) -> None:
    """Open-loop streaming maintenance over the WAL'd ingest loop."""
    import dataclasses as _dc
    import os

    import numpy as np

    from repro.core import BisimMaintainer
    from repro.exmem import (OocBackend, StreamConfig,
                             StreamingMaintenanceService, replay_open_loop,
                             synthesize_ops)
    from repro.quotient import QuotientService

    if not (args.oocore and args.wal and args.workdir):
        raise SystemExit("serve-updates needs --oocore --wal --workdir")
    cfg = StreamConfig(
        batch_ops=args.batch_ops,
        batch_deadline_s=args.batch_deadline_ms / 1e3,
        snapshot_every=args.snapshot_every,
        staleness_batches=args.staleness_batches,
        compact_threshold=args.compact_threshold,
        async_wal=args.async_wal)
    ops = synthesize_ops(args.ops, num_nodes=g.num_nodes, seed=args.seed)

    def _spinup(workdir):
        backend = OocBackend(
            g, chunk_edges=args.chunk_edges, chunk_nodes=args.chunk_nodes,
            spill_threshold=args.spill_threshold, workdir=workdir,
            io_threads=_io_threads(args),
            prefetch_depth=args.prefetch_depth,
            wal=True, wal_group=args.wal_group)
        m = BisimMaintainer(backend, args.k, mode=args.mode,
                            device=args.device_maintenance, wal=True)
        q = (None if args.no_quotient
             else QuotientService(m, workdir, aio=backend.aio))
        return StreamingMaintenanceService(m, config=cfg, quotient=q), \
            backend

    def _print_stats(svc):
        st = svc.stats()
        print(f"stream: {st['applied_ops']} ops in {st['wall_s']:.2f}s "
              f"= {st['updates_per_sec']:.0f} updates/s "
              f"({st['applied_batches']} batches, "
              f"{st['snapshots']} snapshots, {st['rejected']} rejected, "
              f"{st['compactions_scheduled']} compactions, "
              f"{st['rebuilds']} rebuilds)")
        if svc.q is not None:
            ok = st["max_staleness"] <= st["staleness_bound"]
            print(f"staleness: max={st['max_staleness']} batches "
                  f"bound={st['staleness_bound']} "
                  f"{'OK' if ok else 'VIOLATED'} "
                  f"(epoch {st['epoch']})")
            if not ok:
                raise SystemExit("staleness bound violated")
        return st

    if not args.kill_at_op:
        svc, backend = _spinup(args.workdir)
        t0 = time.perf_counter()
        with obs.span("launch.serve", ops=len(ops)):
            replay_open_loop(svc, ops, rate=args.rate or None)
            svc.close()
        _print_stats(svc)
        print(f"serve: wall {time.perf_counter() - t0:.2f}s, "
              f"wal committed lsn {backend._wal.committed_lsn}")
        print(f"workdir: {backend.workdir}")
        return

    # crash drill: reference run, killed run, recover, finish, compare.
    # Compaction scheduling is state-timed, so it is disabled for the
    # drill — a lost (uncommitted) compact record would re-schedule at a
    # different position in the op order and honestly diverge.
    cfg = _dc.replace(cfg, compact_threshold=0.0)
    kill_at = min(int(args.kill_at_op), len(ops))
    ref_svc, ref_backend = _spinup(os.path.join(args.workdir, "ref"))
    replay_open_loop(ref_svc, ops)
    ref_svc.close()
    ref_pids = [np.asarray(ref_svc.m.pids[j]).copy()
                for j in range(ref_svc.m.k + 1)]
    ref_backend.close()

    wd = os.path.join(args.workdir, "live")
    svc, backend = _spinup(wd)
    lsns = replay_open_loop(svc, ops[:kill_at])
    backend.aio.close()   # the "dead" process: no clean close, no drain
    print(f"killed after {kill_at}/{len(ops)} submitted ops "
          f"(last acked lsn {lsns[-1] if lsns else 0})")

    svc2 = StreamingMaintenanceService.recover(
        wd, io_threads=_io_threads(args),
        prefetch_depth=args.prefetch_depth,
        device=args.device_maintenance, config=cfg,
        quotient=not args.no_quotient)
    committed = svc2.m.backend._wal.committed_lsn
    done = sum(1 for lsn in lsns if lsn <= committed)
    print(f"recovered: committed lsn {committed} -> "
          f"{done} ops survived, resubmitting {len(ops) - done}")
    replay_open_loop(svc2, ops[done:])
    svc2.close()
    _print_stats(svc2)
    for j in range(svc2.m.k + 1):
        if not np.array_equal(np.asarray(svc2.m.pids[j]), ref_pids[j]):
            raise SystemExit(
                f"recovery diverged from the uninterrupted run at "
                f"level {j}")
    print("recovery: pid history bit-identical to uninterrupted run")
    svc2.m.backend.close()


def _dispatch(args) -> None:
    if args.cmd == "recover":
        with obs.span("launch.recover"):
            run_recover(args)  # no graph: state comes from the workdir
        return
    if args.cmd == "query":
        with obs.span("launch.query"):
            run_query(args)  # loads its own graph/artifact
        return
    g = make_graph(args)
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges")
    if args.cmd == "materialize":
        with obs.span("launch.materialize"):
            run_materialize(args, g)
        return
    if args.cmd == "serve-updates":
        run_serve(args, g)  # spans live inside the service loop
        return
    if args.cmd:
        with obs.span("launch.update", cmd=args.cmd):
            run_maintenance(args, g)
        return
    engine = ("oocore" if args.oocore else
              "dist/" + args.ranking if args.distributed else "single")
    t0 = time.perf_counter()
    with obs.span("launch.build", engine=engine, k=args.k,
                  mode=args.mode):
        if args.oocore:
            from repro.exmem import build_bisim_oocore
            res = build_bisim_oocore(
                g, args.k, mode=args.mode, chunk_edges=args.chunk_edges,
                chunk_nodes=args.chunk_nodes, workdir=args.workdir,
                spill_threshold=args.spill_threshold,
                early_stop=not args.no_early_stop,
                io_threads=_io_threads(args),
                prefetch_depth=args.prefetch_depth,
                checkpoint=args.checkpoint or args.resume,
                resume=args.resume)
        elif args.distributed:
            res = build_bisim_distributed(
                g, args.k, mode=args.mode, ranking=args.ranking,
                early_stop=not args.no_early_stop)
        else:
            if args.sync_every is not None:
                res = build_bisim(g, args.k, mode=args.mode,
                                  early_stop=not args.no_early_stop,
                                  fused=False, sync_every=args.sync_every)
            else:
                res = build_bisim(g, args.k, mode=args.mode,
                                  early_stop=not args.no_early_stop)
    dt = time.perf_counter() - t0
    print(f"k={args.k} mode={args.mode} {engine}")
    for st in res.stats:
        print(f"  iter {st.iteration:2d}: {st.num_partitions:9d} blocks "
              f"{st.seconds * 1e3:9.1f} ms  sortedB={st.bytes_sorted} "
              f"scannedB={st.bytes_scanned}")
    print(f"total {dt:.2f}s; converged_at={res.converged_at}")
    if args.oocore:
        print(MetricsReport.format_io(res.io.as_dict()))
        _report_overlap(res.aio, sum(s.seconds for s in res.stats))
        if args.workdir:
            print(f"workdir: {res.workdir}")
    if args.out:
        if args.oocore:
            # an .npz is a zip of .npy members: copy the per-level pid
            # files straight in, never materializing the (k+1) x N
            # history the out-of-core engine exists to avoid
            import zipfile
            with zipfile.ZipFile(args.out, "w",
                                 zipfile.ZIP_DEFLATED) as zf:
                for j, p in enumerate(res.pid_paths):
                    zf.write(p, arcname=f"pids_{j}.npy")
        else:
            import numpy as np
            np.savez_compressed(args.out, pids=res.pids)
        print(f"saved pid history to {args.out}")
    if args.oocore and not args.workdir:
        res.cleanup()  # tempdir workdir: don't strand the spilled tables


def main() -> None:
    args = build_parser().parse_args()
    use_compile_cache()
    if not args.trace:
        _dispatch(args)
        return
    tracer = obs.Tracer()
    with obs.tracing(tracer):
        _dispatch(args)
    write_chrome_trace(tracer, args.trace)
    print(f"trace: {args.trace} ({len(tracer.spans)} spans, "
          f"{len(tracer.events)} events)")
    print(MetricsReport.from_tracer(tracer).format())


if __name__ == "__main__":
    main()
