"""One client in a closed loop of `QuotientEngine.query` calls over the
quotient index materialised from the k-level build history.

Traffic parameters: `k`; `call`, the shapes of one call's queries, each
a `kind` (`LabelPath`, `ReachTemplate` or `PointLookup`), `hops` and
`level`; `pool` (queries drawn in set-up, whole calls); `max_batch`
(the engine's wave width); `budget_rows` (the materialisation's);
`sample_answers` (how many of the window's answers are compared);
`limits`.  Every call repeats the same shapes, so each call does the
same work and every seed sends the same shapes, only other labels and
nodes; each query's labels are those of a walk in the graph."""
from __future__ import annotations

import collections
import math
import os
import tempfile
import time

import numpy as np

from bench import bytecount, common, reference

# what the control answers a point lookup with
Point = collections.namedtuple("Point", "node level pid block_size")


class Driver(common.Driver):

    def setup(self) -> None:
        from repro.core import build_bisim
        from repro.quotient import QuotientEngine, materialize_quotient
        self.g = self.make_graph()
        graph = common.to_program_graph(self.g)
        res = build_bisim(graph, self.k, mode=self.mode, early_stop=False)
        self._tmp = tempfile.TemporaryDirectory(prefix="bench-quotient-")
        self.index = materialize_quotient(
            graph, res, os.path.join(self._tmp.name, "q"), mode=self.mode,
            budget_rows=int(self.traffic["budget_rows"]))
        del res, graph
        self.engine = QuotientEngine(self.index,
                                     max_batch=int(self.traffic["max_batch"]))
        self.pool = self._make_pool()
        # one whole call of other queries with the window's shapes:
        # every hop and mask program, and the first call's host work
        self.engine.query(self._queries(
            common.rng_for(self.seed, common.WARMUP), len(self.shapes)))
        self._window()

    def _window(self) -> None:
        self.next = 0
        self.latencies, self.call_s = [], []
        self.calls, self.hop_bytes = 0, 0
        self.kept = common.Reservoir(int(self.traffic["sample_answers"]),
                                     common.rng_for(self.seed, common.SAMPLE))

    # -- traffic
    @property
    def shapes(self) -> list:
        return [(s["kind"], int(s["hops"]), int(s["level"]))
                for s in self.traffic["call"]]

    def _walk(self, rng, off, hops: int) -> tuple:
        g = self.g
        while True:
            cur = int(rng.integers(g.num_nodes))
            labs = []
            for _ in range(hops):
                lo, hi = int(off[cur]), int(off[cur + 1])
                if lo == hi:
                    break
                e = int(rng.integers(lo, hi))
                labs.append(int(g.elabel[e]))
                cur = int(g.dst[e])
            if len(labs) == hops:
                return tuple(labs)

    def _queries(self, rng, count: int) -> list:
        """`count` queries with the call's shapes in turn."""
        from repro.quotient import LabelPath, PointLookup, ReachTemplate
        g = self.g
        off = np.zeros(g.num_nodes + 1, np.int64)
        np.cumsum(np.bincount(g.src, minlength=g.num_nodes), out=off[1:])
        nlab = self.law.num_node_labels
        shapes = self.shapes
        out = []
        for i in range(count):
            kind, hops, level = shapes[i % len(shapes)]
            if kind == "LabelPath":
                out.append(LabelPath(self._walk(rng, off, hops),
                                     level=level))
            elif kind == "ReachTemplate":
                out.append(ReachTemplate(
                    self._walk(rng, off, hops),
                    src_label=int(rng.integers(nlab)),
                    tgt_label=int(rng.integers(nlab)), level=level))
            elif kind == "PointLookup":
                out.append(PointLookup(int(rng.integers(g.num_nodes)),
                                       level))
            else:
                raise ValueError(f"unknown query kind: {kind!r}")
        return out

    def _make_pool(self) -> list:
        count = int(self.traffic["pool"])
        if count % len(self.shapes):
            raise ValueError("the pool must hold whole calls")
        return self._queries(common.rng_for(self.seed, common.TRAFFIC),
                             count)

    def _next_call(self) -> list:
        n = len(self.shapes)
        qs = [self.pool[(self.next + i) % len(self.pool)] for i in range(n)]
        self.next += n
        return qs

    # -- window
    def _wave_bytes(self, queries) -> int:
        """HBM bytes of the hops the engine runs for these queries: it
        buckets path queries by (level, hops) into waves of max_batch."""
        buckets: dict = {}
        for q in queries:
            if hasattr(q, "labels"):
                key = (q.level, len(q.labels))
                buckets[key] = buckets.get(key, 0) + 1
        B = self.engine.max_batch
        total = 0
        for (j, m), n in buckets.items():
            waves = math.ceil(n / B)
            for t in range(m):
                lev = j - t
                total += waves * bytecount.hop_bytes(
                    self.index.levels[lev].num_edges, B,
                    int(self.index.counts[lev - 1]),
                    int(self.index.counts[lev]))
        return total

    def _record(self, qs, answers, dt: float) -> None:
        self.latencies += [dt] * len(qs)
        self.call_s.append(dt)
        self.calls += 1
        for q, a in zip(qs, answers):
            self.kept.offer((q, a))

    def step(self) -> None:
        qs = self._next_call()
        t0 = time.perf_counter()
        answers = self.engine.query(qs)
        self._record(qs, answers, time.perf_counter() - t0)
        self.hop_bytes += self._wave_bytes(qs)

    def end_to_end(self, seconds: float) -> dict:
        lat = sorted(self.latencies)
        p95 = lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
        return {"query_p95_ms": 1000.0 * p95}

    def work(self) -> dict:
        return {"calls": self.calls, "queries": len(self.latencies),
                "hbm_bytes": self.hop_bytes, "call_s": self.call_s}

    def attempted(self) -> int:
        return len(self.latencies)

    def release(self) -> None:
        del self.engine, self.index
        self._tmp.cleanup()

    def check(self):
        sample = self.kept.items
        points = [(q, a) for q, a in sample if not hasattr(q, "labels")]
        levels = (reference.bisim_levels(self.g, self.k, early_stop=False)
                  if points else [])
        wrong = 0
        for q, a in sample:
            if hasattr(q, "labels"):
                want = reference.path_answer(
                    self.g, q.labels, getattr(q, "src_label", None),
                    getattr(q, "tgt_label", None))
                wrong += not np.array_equal(np.asarray(a, np.int64), want)
        # point lookups: the block size, and pids that name the same
        # blocks as the reference's over every lookup of a level
        sizes = [np.bincount(p) for p in levels]
        for q, a in points:
            size = sizes[q.level][levels[q.level][q.node]]
            wrong += (a.node != q.node or a.level != q.level
                      or a.block_size != size)
        pid_gap = 0
        for lev in {q.level for q, _ in points}:
            got = [a.pid for q, a in points if q.level == lev]
            ref = [levels[lev][q.node] for q, _ in points if q.level == lev]
            pid_gap += reference.mismatched_blocks(got, ref)
        values = {"wrong_answers": int(wrong), "point_pid_mismatch": pid_gap}
        return self.checks(values), int(wrong)


def control(d: Driver, steps: int, *, key_bits: int = 32,
            **_options) -> None:
    """The partition keyed at `key_bits` bits, the precision below the
    program's 64-bit signature keys, so that colliding signatures merge
    blocks: `steps` window calls of this seed's queries answered over
    its quotient, in the place of the engine's answers."""
    d.g = d.make_graph()
    d.pool = d._make_pool()
    d._window()
    levels = reference.bisim_levels(d.g, d.k, early_stop=False,
                                    key_bits=key_bits)
    sizes = [np.bincount(p) for p in levels]
    for _ in range(steps):
        qs = d._next_call()
        answers = []
        for q in qs:
            if hasattr(q, "labels"):
                answers.append(reference.quotient_path_answer(
                    d.g, levels, q.level, q.labels,
                    getattr(q, "src_label", None),
                    getattr(q, "tgt_label", None)))
            else:
                pid = int(levels[q.level][q.node])
                answers.append(Point(q.node, q.level, pid,
                                     int(sizes[q.level][pid])))
        d._record(qs, answers, 0.0)
