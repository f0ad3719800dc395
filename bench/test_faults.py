"""The timed path broken underneath a whole run (the look for a chip left
out): each fault a cell can have must turn `correct` false."""
import types

import numpy as np

import repro.core
import repro.quotient
from bench.test_cells import run_toy


def _unchanged_build(real):
    def build(*a, **kw):
        res = real(*a, **kw)
        pids = np.array(res.pids)
        pids[1:] = pids[0]                  # every level left at level 0
        res.pids = pids
        return res
    return build


def _altered_build(real):
    def build(*a, **kw):
        res = real(*a, **kw)
        pids = np.array(res.pids)
        last = pids[-1]
        other = int(np.flatnonzero(last != last[0])[0])
        last[0] = last[other]               # one node moved to another block
        res.pids = pids
        return res
    return build


def test_build_state_unchanged(monkeypatch):
    monkeypatch.setattr(repro.core, "build_bisim",
                        _unchanged_build(repro.core.build_bisim))
    result, _ = run_toy("linkedmdb.build")
    assert not result["correct"]


def test_build_answer_altered(monkeypatch):
    monkeypatch.setattr(repro.core, "build_bisim",
                        _altered_build(repro.core.build_bisim))
    result, _ = run_toy("linkedmdb.build")
    assert not result["correct"]


def test_maintain_batch_left_unapplied(monkeypatch):
    done = types.SimpleNamespace(rebuilt=False, device=True)
    cls = repro.core.BisimMaintainer
    monkeypatch.setattr(cls, "add_edges", lambda self, s, l, d: done)
    monkeypatch.setattr(cls, "delete_edges", lambda self, s, l, d: done)
    result, _ = run_toy("linkedmdb.maintain")
    assert not result["correct"]


def test_maintain_half_the_batch_left_out(monkeypatch):
    cls = repro.core.BisimMaintainer
    add, delete = cls.add_edges, cls.delete_edges

    def half(real):
        def apply(self, s, l, d):
            h = len(s) // 2
            return real(self, s[:h], l[:h], d[:h])
        return apply
    monkeypatch.setattr(cls, "add_edges", half(add))
    monkeypatch.setattr(cls, "delete_edges", half(delete))
    result, _ = run_toy("linkedmdb.maintain")
    assert not result["correct"]


def _engine_fault(real, alter):
    def query(self, queries):
        return [alter(q, a) for q, a in zip(queries, real(self, queries))]
    return query


def test_query_answer_altered(monkeypatch):
    def drop_one(q, a):
        return a[:-1] if hasattr(q, "labels") and len(a) else a
    cls = repro.quotient.QuotientEngine
    monkeypatch.setattr(cls, "query", _engine_fault(cls.query, drop_one))
    result, _ = run_toy("linkedmdb.query")
    assert not result["correct"]


def test_query_state_unchanged(monkeypatch):
    def empty(q, a):
        return np.empty(0, np.int64) if hasattr(q, "labels") else a
    cls = repro.quotient.QuotientEngine
    monkeypatch.setattr(cls, "query", _engine_fault(cls.query, empty))
    result, _ = run_toy("linkedmdb.query")
    assert not result["correct"]


def test_point_lookup_altered(monkeypatch):
    def other_block(q, a):
        if hasattr(q, "labels"):
            return a
        return type(a)(a.node, a.level, a.pid, a.block_size + 1)
    cls = repro.quotient.QuotientEngine
    monkeypatch.setattr(cls, "query", _engine_fault(cls.query, other_block))
    result, _ = run_toy("linkedmdb.query")
    assert not result["correct"]


def test_maintain_leaving_the_device_path(monkeypatch):
    cls = repro.core.BisimMaintainer
    add = cls.add_edges

    def add_on_host(self, s, l, d):
        rep = add(self, s, l, d)
        self.device = False                 # as a degraded maintainer does
        return rep
    monkeypatch.setattr(cls, "add_edges", add_on_host)
    result, _ = run_toy("linkedmdb.maintain")
    assert not result["correct"]
    assert result["checks"]["left_device"]["value"] == 1


def test_point_lookup_pids_merged(monkeypatch):
    def renamed(q, a):
        if hasattr(q, "labels"):
            return a
        return type(a)(a.node, a.level, 0, a.block_size)   # one block
    cls = repro.quotient.QuotientEngine
    monkeypatch.setattr(cls, "query", _engine_fault(cls.query, renamed))
    result, _ = run_toy("linkedmdb.query")
    assert not result["correct"]
    assert result["checks"]["point_pid_mismatch"]["value"] > 0
