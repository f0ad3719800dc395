"""Paper Figs. 7-8: ADD_EDGE behavior and comparison with Build_Bisim.

As in §5.4: pick a random existing edge, build the partition on the rest,
apply ADD_EDGE, and compare with recomputing from scratch.  The oocore
rows run the same protocol through the disk-resident `OocBackend` and
report the per-update IOStats deltas next to an out-of-core rebuild.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import BisimMaintainer, build_bisim
from repro.exmem import OocBackend, build_bisim_oocore
from repro.graph.storage import Graph
from repro.obs import MetricsReport
from repro.obs import tracer as obs

from .datasets import suite


def _holdout(g: Graph, rng) -> tuple:
    """Drop one random edge; return (reduced graph, held-out triple)."""
    i = int(rng.integers(0, g.num_edges))
    keep = np.ones(g.num_edges, bool)
    keep[i] = False
    gg = Graph(g.node_labels, g.src[keep], g.dst[keep], g.elabel[keep])
    return gg, (int(g.src[i]), int(g.elabel[i]), int(g.dst[i]))


def run(scale: int = 1, k: int = 10, trials: int = 3):
    # the head-to-head first: its interleaved timing is the most
    # sensitive row, so it runs before the big builds heat the machine
    rows = list(run_device_vs_host(scale, trials=7))
    for name, g in list(suite(scale).items())[:4]:
        rng = np.random.default_rng(0)
        upd_times, build_times = [], []
        checked = changed = 0
        for t in range(trials):
            gg, (s, l, d) = _holdout(g, rng)
            m = BisimMaintainer(gg, k)
            t0 = time.perf_counter()
            rep = m.add_edge(s, l, d)
            upd_times.append(time.perf_counter() - t0)
            checked += sum(rep.nodes_checked)
            changed += sum(rep.nodes_changed)
            t0 = time.perf_counter()
            build_bisim(g, k)
            build_times.append(time.perf_counter() - t0)
        rows.append((
            f"maintenance/{name}/add_edge",
            float(np.mean(upd_times)) * 1e6,
            f"nodes_checked={checked / trials:.1f};"
            f"nodes_changed={changed / trials:.1f};"
            f"rebuild_us={np.mean(build_times) * 1e6:.0f};"
            f"speedup={np.mean(build_times) / np.mean(upd_times):.2f}x"))
    # oocore: one trial per dataset (the disk build dominates the budget);
    # the update path runs traced so the BENCH payload carries a per-phase
    # breakdown of where maintenance time goes
    tracer = obs.Tracer()
    for name, g in list(suite(scale).items())[:2]:
        rng = np.random.default_rng(0)
        gg, (s, l, d) = _holdout(g, rng)
        backend = OocBackend(gg, chunk_edges=1 << 14)
        m = BisimMaintainer(backend, k)
        io0 = (backend.io.sort_cost, backend.io.scan_cost)
        t0 = time.perf_counter()
        with obs.tracing(tracer):
            rep = m.add_edge(s, l, d)
        dt = time.perf_counter() - t0
        d_sort = backend.io.sort_cost - io0[0]
        d_scan = backend.io.scan_cost - io0[1]
        backend.close()
        t0 = time.perf_counter()
        build_bisim_oocore(g, k, chunk_edges=1 << 14).cleanup()
        dt_build = time.perf_counter() - t0
        rows.append((
            f"maintenance/{name}/add_edge_oocore", dt * 1e6,
            f"nodes_checked={sum(rep.nodes_checked)};"
            f"nodes_changed={sum(rep.nodes_changed)};"
            f"sort_delta={d_sort};scan_delta={d_scan};"
            f"rebuild_us={dt_build * 1e6:.0f};"
            f"speedup={dt_build / dt:.2f}x"))
    report = MetricsReport.from_tracer(tracer).as_dict()
    return rows, {"phases": report["phases"], "levels": report["levels"]}


def run_device_vs_host(scale: int = 1, k: int = 3, trials: int = 7):
    """Device-vs-host propagation head-to-head (ISSUE 5).

    Recompute the signatures of a fixed frontier of existing sources — a
    pure propagation workload: nothing changes, so the run repeats
    bit-identically and the two paths stay in the same state — through
    the host (vectorized numpy) and device (jitted fold + device store
    resolve) paths of the same update-semantics core.  The graph is the
    regime the device path targets (ROADMAP: "very large frontiers"):
    power-law with enough edges that a 2^17-node frontier gathers
    ~500k out-edges per level.

    Frontier sizes are powers of two so the device path's shape buckets
    are exact; the first pass per size is an untimed compile warmup, and
    the two paths are timed *interleaved* (best of `trials` rounds) so
    host load drift cannot bias the comparison either way.
    """
    from repro.core import BisimMaintainer as BM  # local alias for clarity
    from repro.graph import generators as gen
    g = gen.powerlaw_graph(400_000 * scale, 1_600_000 * scale, 2, 2,
                           seed=9)
    uniq_src = np.unique(g.src)
    rng = np.random.default_rng(1)
    rows = []
    for mode in ("multiset", "sorted"):
        # rebuild_threshold > 1: the largest frontier must propagate,
        # not trip the §4.2 switch-back
        m_host = BM(g, k, rebuild_threshold=2.0, mode=mode)
        m_dev = BM(g, k, rebuild_threshold=2.0, mode=mode, device=True)
        for size in (1 << 12, 1 << 14, 1 << 17):
            if size > uniq_src.size:
                break
            frontier = np.sort(rng.choice(uniq_src, size, replace=False))
            frontier = frontier.astype(np.int64)
            m_dev._propagate(frontier)   # compile warmup for this bucket
            m_host._propagate(frontier)  # same treatment (cache warmth)
            host_s, dev_s = 9e9, 9e9
            for _ in range(trials):
                host_s = min(host_s, _timed(m_host, frontier))
                dev_s = min(dev_s, _timed(m_dev, frontier))
            # one traced propagate for the dispatch/sync columns: the
            # fused k-loop steady state is 1 dispatch + 1 scalar sync
            # for the whole level ladder
            t = obs.Tracer()
            with obs.tracing(t):
                m_dev._propagate(frontier)
            rows.append((
                f"maintenance/powerlaw1p6M/{mode}/propagate_device_f{size}",
                dev_s * 1e6,
                f"frontier={size};host_us={host_s * 1e6:.0f};"
                f"device_us={dev_s * 1e6:.0f};"
                f"speedup={host_s / dev_s:.2f}x;"
                f"dispatches={len(t.find_events('maint.dispatch'))};"
                f"sync_count={len(t.find('maint.sync'))}"))
    return rows


def _timed(m, frontier) -> float:
    t0 = time.perf_counter()
    m._propagate(frontier)
    return time.perf_counter() - t0
