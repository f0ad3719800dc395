#!/usr/bin/env python3
"""Smoke run of the system's main path on one TPU chip, at LinkedMDB size.

Builds, maintains and serves the k-bisimulation partition of a graph of
the `linkedmdb-like` family (`benchmarks/datasets.py`) at scale 100 —
2.3M nodes and about 6.1M labelled edges, LinkedMDB's published size —
through the library calls the launcher's subcommands make, and checks
every result against a plain reference:

  kernels   the three Pallas folds, compiled, vs the numpy fold
  build     build_bisim (fused, k=10) cold then warm, vs a numpy k-loop;
            the exact oracle on a 20k-node graph of the same family
            certifies that the 64-bit signature hashes do not collide
  oocore    build_bisim_oocore with 1M-edge chunks vs the in-memory build
  maintain  BisimMaintainer(device=True): 4 x 1024 edge inserts and one
            node delete, each vs a fresh numpy rebuild; one batch also
            through OocBackend
  query     materialize the quotient of the maintained history; 64 mixed
            queries through QuotientEngine, in waves, vs eval_ref

Each phase prints one JSON line: wall seconds, the seconds spent
compiling (first calls), and the device's peak_bytes_in_use.  The last
line is {"ok": true, "device": {"platform", "kind", "count"}}.  A run
that finds no TPU exits non-zero before any phase and prints no result;
there is no CPU fallback.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --four-chips   # only the sharded build, 4 chips

With --four-chips the script runs build_bisim_distributed over four
chips with both rankings and compares every level with the single-chip
build_bisim; it prints where each sharded input lives and every chip's
peak bytes.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K_BUILD = 10
K_MAINT = 5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Graph and traffic sizes; the defaults are the chip run's."""
    nodes: int = 2_300_000          # linkedmdb-like at scale 100
    edges: int = 6_100_000
    oracle_nodes: int = 20_000      # the exact oracle's graph
    chunk_edges: int = 1 << 20      # oocore chunk: >= 6 device folds
    batches: int = 4
    batch_edges: int = 1024
    queries: int = 64
    wave: int = 16
    kernel_chunk: int = 1 << 16     # build_bisim_oocore's default chunk
    kernel_frontier: int = 1 << 14  # a maintenance frontier edge bucket


def linkedmdb_like(nodes: int, edges: int, seed: int):
    """The suite's linkedmdb-like family (6 node labels, 12 edge
    labels, uniform endpoints), generated directly at this size."""
    from repro.graph import generators as gen
    return gen.random_graph(nodes, edges, 6, 12, seed=seed)


# ------------------------------------------------------------- reporting
class _CompileClock:
    """Seconds jax spends tracing, lowering and compiling, summed from
    its own monitoring events (from any thread)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration
                if event.endswith("backend_compile_duration"):
                    self.compiles += 1


_CLOCK = _CompileClock()


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def phase(name: str):
    """Time a phase and print its line when it completes; an exception
    propagates (nothing is printed for a failed phase)."""
    info: dict = {}
    c0, n0, t0 = _CLOCK.seconds, _CLOCK.compiles, time.perf_counter()
    yield info
    line = {"phase": name,
            "seconds": time.perf_counter() - t0,
            "compile_s": _CLOCK.seconds - c0,
            "compiles": _CLOCK.compiles - n0,
            "peak_bytes_in_use": _peak_bytes()}
    line.update(info)
    print(json.dumps(line), flush=True)


# ------------------------------------------------------------ references
def reference_pids(g, k: int, *, early_stop: bool = True) -> list:
    """Plain numpy k-loop — `hashes_np` signatures and `np.unique`
    ranking, no JAX — with build_bisim's early-stop rule."""
    from repro.core import hashes_np
    pid0 = np.unique(g.node_labels, return_inverse=True)[1].astype(np.int64)
    hist = [pid0]
    counts = [int(pid0.max()) + 1 if pid0.size else 0]
    for _ in range(k):
        hi, lo = hashes_np.signatures_from_edges(
            pid0, g.src, g.elabel, hist[-1][g.dst], g.num_nodes)
        key = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
        uniq, pid = np.unique(key, return_inverse=True)
        hist.append(pid.astype(np.int64))
        counts.append(len(uniq))
        if early_stop and counts[-1] == counts[-2]:
            break
    return hist


def assert_levels(got, want, what: str) -> None:
    from repro.core import same_partition
    assert len(got) == len(want), (what, len(got), len(want))
    for j, (a, b) in enumerate(zip(got, want)):
        assert same_partition(a, b), f"{what}: level {j} differs"


def _fold_ref(lab, tgt, seg, keep, num_segments: int):
    from repro.core import hashes_np
    hi = np.zeros(num_segments, np.uint32)
    lo = np.zeros(num_segments, np.uint32)
    sel = keep & (seg < num_segments)
    e_hi, e_lo = hashes_np.hash_pair(lab[sel], tgt[sel])
    with np.errstate(over="ignore"):
        np.add.at(hi, seg[sel], e_hi)
        np.add.at(lo, seg[sel], e_lo)
    return hi, lo


def _sorted_batch(rng, e: int, num_segments: int, fill: float):
    """A (seg, eLabel, pId)-sorted batch with tail padding, as the
    maintenance gathers and the oocore run merge hand it over."""
    seg = np.sort(rng.integers(0, num_segments, e)).astype(np.int32)
    lab = rng.integers(0, 12, e).astype(np.int32)
    tgt = rng.integers(0, 40, e).astype(np.int32)
    order = np.lexsort((tgt, lab, seg))
    seg, lab, tgt = seg[order], lab[order], tgt[order]
    n = int(e * fill)
    valid = np.arange(e) < n
    seg[n:] = num_segments          # padding: past every real segment
    dup = np.zeros(e, bool)
    dup[1:] = ((seg[1:] == seg[:-1]) & (lab[1:] == lab[:-1])
               & (tgt[1:] == tgt[:-1]))
    return seg, lab, tgt, valid, dup


def _equal(got, want) -> bool:
    return all(np.array_equal(np.asarray(a), b) for a, b in zip(got, want))


# ---------------------------------------------------------------- phases
def run_kernels(sizes: Sizes, seed: int) -> None:
    """Each Pallas fold at its callers' width vs the numpy fold."""
    from repro.kernels import ops
    from repro.kernels.sig_fold import chunk_sig_fold, frontier_sig_fold
    rng = np.random.default_rng(seed + 1)
    with phase("kernels") as info:
        e = sizes.kernel_chunk
        seg, lab, tgt, valid, dup = _sorted_batch(rng, e, e // 3, 0.9)
        for keep0 in (True, False):
            keep = valid & ~dup
            keep[0] = keep0
            got = chunk_sig_fold(lab, tgt, seg, valid, np.asarray([keep0]),
                                 num_segments=e, dedup=True)
            assert _equal(got, _fold_ref(lab, tgt, seg, keep, e)), \
                f"chunk_sig_fold keep0={keep0}"
        e = sizes.kernel_frontier
        ns = e // 4
        seg, lab, tgt, valid, dup = _sorted_batch(rng, e, ns, 0.75)
        for dedup in (False, True):
            keep = valid & ~dup if dedup else valid
            got = frontier_sig_fold(lab.view(np.uint32), tgt.view(np.uint32),
                                    seg, valid, num_sigs=ns, dedup=dedup)
            assert _equal(got, _fold_ref(lab, tgt, seg, keep, ns)), \
                f"frontier_sig_fold dedup={dedup}"
        g = linkedmdb_like(e // 3, e, seed + 2)
        lay = ops.blocked_csr_layout(g.src, g.dst, g.elabel, g.num_nodes,
                                     nodes_per_block=8)
        pid = (np.arange(g.num_nodes) % 97).astype(np.int32)
        got = ops.sig_fold_from_layout(
            lay["elabel"], lay["dst"], lay["local_src"], lay["valid"], pid,
            nodes_per_block=8, edges_per_block=lay["edges_per_block"],
            num_nodes=g.num_nodes)
        want = _fold_ref(g.elabel, pid[g.dst], g.src,
                         np.ones(g.num_edges, bool), g.num_nodes)
        assert _equal(got, want), "sig_fold (blocked CSR)"
        info.update(chunk_lanes=sizes.kernel_chunk,
                    frontier_lanes=sizes.kernel_frontier,
                    blocked_edges_per_block=int(lay["edges_per_block"]))


def run_build(g, sizes: Sizes, seed: int):
    from repro.core import build_bisim, oracle_pids
    with phase("build") as info:
        c0 = _CLOCK.seconds
        t0 = time.perf_counter()
        res = build_bisim(g, K_BUILD, mode="sorted")
        cold = time.perf_counter() - t0
        cold_compile = _CLOCK.seconds - c0
        t0 = time.perf_counter()
        again = build_bisim(g, K_BUILD, mode="sorted")
        warm = time.perf_counter() - t0
        assert np.array_equal(res.pids, again.pids)
        assert_levels(list(res.pids), reference_pids(g, K_BUILD),
                      "build_bisim vs numpy")
        # hash-collision certificate: the exact (hash-free) oracle on a
        # graph of the same family small enough for pure Python
        small = linkedmdb_like(sizes.oracle_nodes,
                               sizes.oracle_nodes * sizes.edges
                               // sizes.nodes, seed + 3)
        assert_levels(list(build_bisim(small, K_BUILD, mode="sorted").pids),
                      oracle_pids(small, K_BUILD), "build_bisim vs oracle")
        info.update(nodes=g.num_nodes, edges=g.num_edges,
                    levels=res.k_effective, counts=res.counts,
                    cold_s=cold, cold_compile_s=cold_compile, warm_s=warm,
                    warm_edges_per_s=g.num_edges * res.k_effective / warm)
    return res


def run_oocore(g, res, sizes: Sizes) -> None:
    from repro.exmem import IOStats, build_bisim_oocore
    from repro.obs import tracer as obs
    with phase("oocore") as info, \
            tempfile.TemporaryDirectory(prefix="smoke-oocore-") as wd:
        io = IOStats()
        tracer = obs.Tracer()
        with obs.tracing(tracer):
            ores = build_bisim_oocore(g, K_BUILD, mode="sorted",
                                      chunk_edges=sizes.chunk_edges,
                                      workdir=wd, stats=io)
        assert_levels([ores.pid_at(j) for j in range(ores.k_effective + 1)],
                      list(res.pids), "oocore vs in-memory")
        folds = len(tracer.find("build.fold"))
        assert folds >= 6 * ores.k_effective, folds
        info.update(levels=ores.k_effective, device_folds=folds,
                    **io.to_dict())


def _random_edges(rng, n: int, count: int):
    src = rng.integers(0, n, count).astype(np.int32)
    lab = rng.integers(0, 12, count).astype(np.int32)
    dst = rng.integers(0, n, count).astype(np.int32)
    return src, lab, dst


def run_maintain(g, sizes: Sizes, seed: int):
    from repro.core import BisimMaintainer
    from repro.exmem import OocBackend
    from repro.obs import tracer as obs
    rng = np.random.default_rng(seed + 4)
    with phase("maintain") as info, \
            tempfile.TemporaryDirectory(prefix="smoke-maint-") as wd:
        tracer = obs.Tracer()
        step_s = []
        with obs.tracing(tracer):
            m = BisimMaintainer(g, K_MAINT, device=True)
            assert m.device
            ooc = BisimMaintainer(
                OocBackend(g, workdir=wd, chunk_edges=sizes.chunk_edges),
                K_MAINT, device=True)
            for b in range(sizes.batches):
                src, lab, dst = _random_edges(rng, g.num_nodes,
                                              sizes.batch_edges)
                t0 = time.perf_counter()
                rep = m.add_edges(src, lab, dst)
                step_s.append(time.perf_counter() - t0)
                assert rep.device, f"batch {b} left the device path"
                ref = reference_pids(m.graph, K_MAINT, early_stop=False)
                assert_levels(m.pids, ref, f"insert batch {b}")
                if b == 0:
                    assert ooc.add_edges(src, lab, dst).device
                    assert_levels(ooc.pids, ref, "OocBackend batch 0")
                    ooc.backend.close()
            t0 = time.perf_counter()
            rep = m.delete_node(int(src[0]))
            step_s.append(time.perf_counter() - t0)
            assert rep.device
            assert_levels(m.pids, reference_pids(m.graph, K_MAINT,
                                                 early_stop=False),
                          "delete_node")
        assert m.device, "maintenance fell back to the host"
        dispatches = len(tracer.find_events("maint.dispatch"))
        assert dispatches > 0
        info.update(k=K_MAINT, batch_edges=sizes.batch_edges,
                    step_s=step_s, maint_dispatches=dispatches)
    return m


def _walk(rng, g, off, length: int):
    """Edge labels of a random walk: a path with a witness."""
    while True:
        cur = int(rng.integers(g.num_nodes))
        labs = []
        for _ in range(length):
            lo, hi = int(off[cur]), int(off[cur + 1])
            if lo == hi:
                break
            e = int(rng.integers(lo, hi))
            labs.append(int(g.elabel[e]))
            cur = int(g.dst[e])
        if len(labs) == length:
            return tuple(labs)


def make_queries(g, rng, count: int, k: int) -> list:
    from repro.quotient import LabelPath, PointLookup, ReachTemplate
    off = g.out_offsets()
    out = []
    for i in range(count):
        hops = 1 + i % 3
        level = int(rng.integers(hops, k + 1))
        if i % 3 == 0:
            out.append(LabelPath(_walk(rng, g, off, hops), level=level))
        elif i % 3 == 1:
            out.append(ReachTemplate(_walk(rng, g, off, hops),
                                     src_label=int(rng.integers(6)),
                                     tgt_label=int(rng.integers(6)),
                                     level=level))
        else:
            out.append(PointLookup(int(rng.integers(g.num_nodes)), level))
    return out


def run_query(m, sizes: Sizes, seed: int) -> None:
    from repro.quotient import (PointLookup, QuotientEngine, eval_ref,
                                materialize_quotient)
    rng = np.random.default_rng(seed + 5)
    with phase("query") as info, \
            tempfile.TemporaryDirectory(prefix="smoke-quotient-") as qd:
        t0 = time.perf_counter()
        index = materialize_quotient(
            m.graph, m.backend, os.path.join(qd, "q"),
            counts=[int(x) for x in m.next_pid], mode=m.mode,
            budget_rows=sizes.chunk_edges)
        materialize_s = time.perf_counter() - t0
        engine = QuotientEngine(index, max_batch=sizes.wave)
        queries = make_queries(m.graph, rng, sizes.queries, m.k)
        nonempty = 0
        wave_s = []
        for w0 in range(0, len(queries), sizes.wave):
            wave = queries[w0:w0 + sizes.wave]
            t0 = time.perf_counter()
            answers = engine.query(wave)
            wave_s.append(time.perf_counter() - t0)
            for q, a in zip(wave, answers):
                want = eval_ref(index, q)
                if isinstance(q, PointLookup):
                    assert a == want, (q, a, want)
                else:
                    assert np.array_equal(a, want), q
                    nonempty += bool(len(a))
        assert nonempty, "every path query came back empty"
        info.update(blocks=[int(c) for c in index.counts],
                    quotient_edges=[index.levels[j].num_edges
                                    for j in range(1, index.k + 1)],
                    materialize_s=materialize_s, wave_s=wave_s,
                    nonempty_path_answers=nonempty, **engine.stats)


def run_one_chip(sizes: Sizes, seed: int) -> None:
    run_kernels(sizes, seed)
    with phase("graph") as info:
        g = linkedmdb_like(sizes.nodes, sizes.edges, seed)
        info.update(nodes=g.num_nodes, edges=g.num_edges)
    res = run_build(g, sizes, seed)
    run_oocore(g, res, sizes)
    del res
    m = run_maintain(g, sizes, seed)
    run_query(m, sizes, seed)


def run_four_chips(sizes: Sizes, seed: int, devices) -> None:
    """The sharded build over four chips, both rankings, vs the
    single-chip build — and nothing else."""
    from repro.core import build_bisim, build_bisim_distributed
    from repro.core.distributed import (make_flat_mesh, place_sharded,
                                        shard_graph)
    g = linkedmdb_like(sizes.nodes, sizes.edges, seed)
    mesh = make_flat_mesh(devices)
    axis = ("devices",)
    # the three programs compile side by side (XLA compiles outside the
    # GIL): most of a cold run is compiling, and every second of it
    # holds four chips.  One sharded iteration compiles the same step
    # program the k-loop reuses.
    with phase("compile"), ThreadPoolExecutor(3) as pool:
        warm = [pool.submit(build_bisim_distributed, g, 1, mesh=mesh,
                            axis=axis, ranking=r)
                for r in ("allgather", "bucketed")]
        warm.append(pool.submit(build_bisim, g, K_BUILD, mode="sorted"))
        for w in warm:
            w.result()
    results = {}
    for ranking in ("allgather", "bucketed"):
        with phase(f"sharded_build_{ranking}") as info:
            results[ranking] = build_bisim_distributed(
                g, K_BUILD, mesh=mesh, axis=axis, ranking=ranking)
            info.update(nodes=g.num_nodes, edges=g.num_edges,
                        counts=results[ranking].counts)
    with phase("sharded_placement") as info:
        placed = place_sharded(shard_graph(g, len(devices)), mesh, axis)
        where = {}
        for name, arr in placed.items():
            ids = sorted(d.id for d in arr.sharding.device_set)
            shards = {s.device.id: s.data.shape[0]
                      for s in arr.addressable_shards}
            assert ids == sorted(d.id for d in devices), (name, ids)
            assert set(shards.values()) == {arr.shape[0] // len(devices)}
            where[name] = {"device_set": ids, "shard_rows": shards}
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        assert all(p for p in peaks) or peaks[0] is None, peaks
        info.update(inputs=where, peak_bytes_in_use_per_device=peaks)
    with phase("single_chip_build") as info:
        single = build_bisim(g, K_BUILD, mode="sorted")
        for ranking, res in results.items():
            assert_levels(list(res.pids), list(single.pids),
                          f"sharded ({ranking}) vs single chip")
        info.update(levels=single.k_effective, counts=single.counts)


# ------------------------------------------------------------------ main
def device_check(want: int) -> dict:
    """The platform must be a TPU with at least `want` chips; print and
    return what jax reports.  Exits non-zero otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: jax reports {devs[0].platform!r} devices, "
                 "not a TPU; this smoke run needs the chip")
    if len(devs) < want:
        sys.exit(f"chip_smoke: {len(devs)} TPU devices, {want} needed")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(json.dumps({"phase": "device", **info,
                      "jax": jax.__version__}), flush=True)
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded build over four chips")
    args = ap.parse_args(argv)
    device = device_check(4 if args.four_chips else 1)
    try:
        from repro.compat import use_compile_cache
    except ImportError as exc:
        sys.exit(f"chip_smoke: the repro package is not under "
                 f"{os.path.join(ROOT, 'src')} ({exc})")
    import jax
    print(json.dumps({"phase": "compile_cache",
                      "dir": use_compile_cache()}), flush=True)
    jax.monitoring.register_event_duration_secs_listener(_CLOCK)
    if args.four_chips:
        run_four_chips(Sizes(), args.seed, jax.devices()[:4])
    else:
        run_one_chip(Sizes(), args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
