"""Observability (repro.obs): tracer correctness, exporter schemas, and
the off-by-default contract — tracing must never change partitions or
IOStats, and no tracer installed must cost one branch per span."""
import json
import threading
import time

import numpy as np
import pytest

from repro.core import (BisimMaintainer, FaultPlan, MaintenanceReport,
                        install_fault_plan)
from repro.exmem import AioStats, IOStats, OocBackend, build_bisim_oocore
from repro.exmem.aio import live_aio_threads
from repro.graph import generators as gen
from repro.obs import (NOOP_SPAN, MetricsReport, Tracer, chrome_trace,
                       current_tracer, tracing, validate_chrome_trace,
                       write_chrome_trace)
from repro.obs import tracer as obs

MODES = ["sorted", "dedup_hash", "multiset"]


def _graphs():
    return [("structured", gen.structured_graph(200, seed=3)),
            ("random", gen.random_graph(500, 1500, 4, 3, seed=7))]


def _assert_no_aio_threads(timeout: float = 2.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not live_aio_threads():
            return
        time.sleep(0.01)
    assert live_aio_threads() == []


# ------------------------------------------------------------- span core
def test_span_nesting_depth_and_parent():
    t = Tracer()
    with t.span("outer.a"):
        with t.span("inner.b", rows=3) as sp:
            sp.set(extra=1)
        with t.span("inner.c"):
            pass
    by_name = {s["name"]: s for s in t.spans}
    assert by_name["outer.a"]["depth"] == 0
    assert by_name["outer.a"]["parent"] is None
    assert by_name["inner.b"]["depth"] == 1
    assert by_name["inner.b"]["parent"] == "outer.a"
    assert by_name["inner.b"]["attrs"] == {"rows": 3, "extra": 1}
    # children finish before the parent; all durations are positive
    assert all(s["dur"] > 0 for s in t.spans)
    assert by_name["inner.b"]["ts"] >= by_name["outer.a"]["ts"]


def test_span_records_exception_and_unwinds_stack():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("x.fail"):
            raise ValueError("boom")
    assert t.spans[0]["attrs"]["error"] == "ValueError"
    assert t.current() is None


def test_span_io_delta_attachment():
    t = Tracer()
    io = IOStats()
    with t.span("x.charged", io=io):
        io.count_sort(10, 80)
        io.count_scan(5, 20)
    attrs = t.spans[0]["attrs"]
    assert attrs["io.sort_cost"] == 10
    assert attrs["io.sort_bytes"] == 80
    assert attrs["io.scan_cost"] == 5
    # zero deltas are not attached
    assert "io.spills" not in attrs


def test_spans_thread_safe_per_thread_stacks():
    t = Tracer()
    errs = []

    def worker(i):
        try:
            for _ in range(50):
                with t.span(f"w.outer", worker=i):
                    with t.span(f"w.inner", worker=i):
                        pass
        except Exception as exc:  # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=worker, args=(i,), name=f"obs-w{i}")
               for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    assert len(t.spans) == 4 * 50 * 2
    inner = t.find("w.inner")
    # nesting resolved per thread: every inner span has the right parent
    # and carries its own thread's identity
    assert all(s["parent"] == "w.outer" and s["depth"] == 1 for s in inner)
    assert {s["tname"] for s in inner} == {f"obs-w{i}" for i in range(4)}


def test_events_record_enclosing_span():
    t = Tracer()
    with t.span("a.b"):
        t.event("ev.inside", n=1)
    t.event("ev.outside")
    assert t.find_events("ev.inside")[0]["span"] == "a.b"
    assert t.find_events("ev.outside")[0]["span"] is None


def test_global_tracer_install_and_noop():
    assert current_tracer() is None
    assert obs.span("x.y") is NOOP_SPAN
    obs.event("x.ev")  # no-op, no error
    with tracing() as t:
        assert current_tracer() is t
        with obs.span("x.y"):
            obs.event("x.ev")
    assert current_tracer() is None
    assert len(t.spans) == 1 and len(t.events) == 1


def test_noop_span_overhead_micro():
    """With no tracer installed a span is one global read + one branch;
    1e5 of them must cost well under a second even on a loaded CI box."""
    assert current_tracer() is None
    t0 = time.perf_counter()
    for _ in range(100_000):
        with obs.span("hot.loop"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"no-op span path too slow: {dt:.3f}s / 1e5 spans"


def test_tracer_caps_records():
    t = Tracer(max_records=10)
    for i in range(20):
        with t.span("x.s"):
            pass
        t.event("x.e")
    assert len(t.spans) == 10 and len(t.events) == 10
    assert t.dropped == 20


# ------------------------------------------------------------- exporters
def test_chrome_trace_schema_and_roundtrip(tmp_path):
    t = Tracer()
    with t.span("build.level", level=0, rows=np.int64(7)):
        with t.span("build.fold", level=0):
            t.event("fault.point", kind="read", index=np.int32(1))
    path = str(tmp_path / "trace.json")
    obj = write_chrome_trace(t, path)
    assert validate_chrome_trace(obj)
    loaded = json.load(open(path))
    assert validate_chrome_trace(loaded)
    xs = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"build.level", "build.fold"}
    # numpy attr values were coerced to plain JSON ints
    lvl = next(e for e in xs if e["name"] == "build.level")
    assert lvl["args"]["rows"] == 7 and isinstance(lvl["args"]["rows"], int)
    assert lvl["cat"] == "build"
    instants = [e for e in loaded["traceEvents"] if e["ph"] == "i"]
    assert instants[0]["name"] == "fault.point"
    assert instants[0]["args"]["span"] == "build.fold"
    meta = [e for e in loaded["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "thread_name" for e in meta)


def test_validate_chrome_trace_rejects_bad_objects():
    with pytest.raises(ValueError):
        validate_chrome_trace([])
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "Z",
                                               "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                               "pid": 1, "tid": 1,
                                                "ts": -1, "dur": 1}]})


def test_metrics_report_aggregates_and_merges():
    t = Tracer()
    for lvl in (0, 1, 1):
        with t.span("build.fold", level=lvl):
            pass
    with t.span("sort.merge_pass"):
        pass
    rep = MetricsReport.from_tracer(t)
    assert rep.phases["build.fold"]["count"] == 3
    assert set(rep.levels) == {0, 1}
    assert rep.levels[1]["build.fold"] > 0
    d = rep.as_dict()
    assert set(d["levels"]) == {"0", "1"}
    json.dumps(d)  # payload must be JSON-clean
    other = MetricsReport.from_tracer(t)
    merged = rep.merge(other)
    assert merged is rep
    assert rep.phases["build.fold"]["count"] == 6
    assert rep.span_count == 8
    text = rep.format()
    assert "build.fold" in text and "per level:" in text


def test_metrics_report_io_and_overlap_text_contract():
    io = IOStats()
    io.count_sort(3, 24)
    io.count_scan(2, 8)
    line = MetricsReport.format_io(io.as_dict())
    assert line == ("io: sort_cost=3 scan_cost=2 sortB=24 scanB=8 "
                    "runs=0 merges=0 spills=0")
    assert MetricsReport.format_overlap(None, 1.0) is None
    aio = AioStats()
    aio.add_read_wait(0.25)
    aio.add_written(64)
    line = MetricsReport.format_overlap(aio.as_dict(), 1.5)
    assert line == ("overlap: read_wait=0.250s write_wait=0.000s "
                    "fold+rank=1.500s prefetched=1 streamed_writes=1")


# ----------------------------------------------------- stats uniformity
def test_stats_as_dict_and_merge():
    a, b = IOStats(), IOStats()
    a.count_sort(2, 16)
    b.count_sort(3, 24)
    b.count_scan(1, 4)
    b.bump("spills")
    a.merge(b)
    d = a.as_dict()
    assert d["sort_cost"] == 5 and d["sort_bytes"] == 40
    assert d["scan_cost"] == 1 and d["spills"] == 1

    s1, s2 = AioStats(), AioStats()
    s1.add_read_wait(0.5)
    s2.add_read_wait(0.25)
    s2.add_written(64)
    s1.merge(s2)
    d = s1.as_dict()
    assert d["read_wait_s"] == 0.75 and d["chunks_written"] == 1
    assert d["chunks_prefetched"] == 2 and d["bytes_written"] == 64

    r1 = MaintenanceReport([1, 2], [1, 0], [2, 2],
                           level_seconds=[0.1, 0.2])
    r2 = MaintenanceReport([2, 2, 5], [0, 1, 1], [1, 1, 1], rebuilt=True,
                           level_seconds=[0.1, 0.1, 0.1], device=True)
    r1.merge(r2)
    d = r1.as_dict()
    assert d["nodes_checked"] == [3, 4, 5]
    assert d["rebuilt"] is True
    assert d["device"] is False  # ANDed: one host batch in the mix
    assert d["level_seconds"] == pytest.approx([0.2, 0.3, 0.1])


# ------------------------------------- tracing is contract-neutral
@pytest.mark.parametrize("mode", MODES)
def test_build_bit_identical_with_tracing(tmp_path, mode):
    """Tracing on vs off: identical pid history per level AND exactly
    equal IOStats, for every signature mode and two generators."""
    for gname, g in _graphs():
        res_off = build_bisim_oocore(
            g, 3, mode=mode, chunk_edges=256, spill_threshold=64,
            workdir=str(tmp_path / f"off_{mode}_{gname}"))
        tracer = Tracer()
        with tracing(tracer):
            res_on = build_bisim_oocore(
                g, 3, mode=mode, chunk_edges=256, spill_threshold=64,
                workdir=str(tmp_path / f"on_{mode}_{gname}"))
        assert res_on.io.to_dict() == res_off.io.to_dict(), \
            f"IOStats diverged under tracing ({gname}, {mode})"
        assert res_on.converged_at == res_off.converged_at
        for j, (pa, pb) in enumerate(zip(res_off.pid_paths,
                                         res_on.pid_paths)):
            np.testing.assert_array_equal(
                np.load(pa), np.load(pb),
                err_msg=f"pid_{j} diverged under tracing ({gname}, {mode})")
        # and the traced run actually produced the tentpole phase spans
        for name in ("build.level", "build.fold", "build.rank",
                     "build.pid_write", "store.resolve"):
            assert tracer.find(name), f"no {name} spans ({gname}, {mode})"
    _assert_no_aio_threads()


def test_maintenance_bit_identical_with_tracing():
    g = gen.structured_graph(200, seed=3)
    rng_args = dict(chunk_edges=256, spill_threshold=64)

    def _run(traced):
        backend = OocBackend(g, **rng_args)
        m = BisimMaintainer(backend, 3)
        rng = np.random.default_rng(11)
        n = backend.num_nodes
        src = rng.integers(0, n, 6).astype(np.int32)
        dst = rng.integers(0, n, 6).astype(np.int32)
        lab = rng.integers(0, 3, 6).astype(np.int32)
        if traced:
            tracer = Tracer()
            with tracing(tracer):
                rep = m.add_edges(src, lab, dst)
        else:
            tracer, rep = None, m.add_edges(src, lab, dst)
        pid = m.pid().copy()
        io = backend.io.to_dict()
        backend.close()
        return pid, io, rep.as_dict(), tracer

    pid_off, io_off, rep_off, _ = _run(False)
    pid_on, io_on, rep_on, tracer = _run(True)
    np.testing.assert_array_equal(pid_off, pid_on)
    assert io_off == io_on
    # level_seconds are wall-clock; everything else must match exactly
    rep_off.pop("level_seconds"), rep_on.pop("level_seconds")
    assert rep_off == rep_on
    assert tracer.find("maint.propagate") and tracer.find("maint.level")
    _assert_no_aio_threads()


def test_no_thread_leak_with_tracing_enabled():
    g = gen.structured_graph(150, seed=1)
    with tracing() as t:
        res = build_bisim_oocore(g, 3, chunk_edges=256, io_threads=2,
                                 prefetch_depth=1)
        res.cleanup()
    _assert_no_aio_threads()
    # worker lanes made it into the trace (reader and writer threads)
    tnames = {s["tname"] for s in t.spans}
    assert any(n.startswith("exmem-aio-reader") for n in tnames)
    assert any(n.startswith("exmem-aio-writer") for n in tnames)


def test_fault_events_appear_in_export(tmp_path):
    g = gen.structured_graph(150, seed=1)
    with tracing() as t, install_fault_plan(FaultPlan()) as plan:
        res = build_bisim_oocore(g, 2, chunk_edges=256, io_threads=0,
                                 workdir=str(tmp_path / "wd"))
    assert plan.points_seen > 0
    pts = t.find_events("fault.point")
    assert len(pts) == plan.points_seen
    obj = chrome_trace(t)
    assert validate_chrome_trace(obj)
    instants = [e for e in obj["traceEvents"]
                if e["ph"] == "i" and e["name"] == "fault.point"]
    assert len(instants) == plan.points_seen
    assert all(e["cat"] == "fault" for e in instants)
    assert instants[0]["args"]["kind"]


def test_retry_events_traced():
    from repro.core.faults import TransientIOError, with_retries
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientIOError("flaky")
        return "ok"

    with tracing() as t:
        assert with_retries(flaky, backoff_s=0.0) == "ok"
    retries = t.find_events("fault.retry")
    assert [e["attrs"]["attempt"] for e in retries] == [1, 2]


def test_quotient_serving_bit_identical_with_tracing(tmp_path):
    """The quotient subsystem under tracing: identical query answers,
    identical patched artifact, exactly equal IOStats — and the traced
    run emits the materialize/patch/query_wave spans + epoch events."""
    from repro.quotient import LabelPath, PointLookup, QuotientService

    g = gen.structured_graph(60, seed=9)
    queries = [LabelPath((0, 1), level=3), LabelPath((2,), level=1),
               PointLookup(5, 3)]

    def _run(traced, sub):
        backend = OocBackend(g, chunk_edges=256,
                             workdir=str(tmp_path / sub / "b"))
        m = BisimMaintainer(backend, 3)
        rng = np.random.default_rng(21)

        def _drive():
            svc = QuotientService(m, str(tmp_path / sub), max_batch=2)
            a0 = svc.query(queries)
            n = backend.num_nodes
            svc.add_edges(rng.integers(0, n, 5).astype(np.int32),
                          rng.integers(0, 3, 5).astype(np.int32),
                          rng.integers(0, n, 5).astype(np.int32))
            return svc, a0, svc.query(queries)

        if traced:
            tracer = Tracer()
            with tracing(tracer):
                svc, a0, a1 = _drive()
        else:
            tracer, (svc, a0, a1) = None, _drive()
        io = dict(sort_cost=svc.io.sort_cost, scan_cost=svc.io.scan_cost,
                  sort_bytes=svc.io.sort_bytes,
                  scan_bytes=svc.io.scan_bytes)
        runs = [(svc.index.runs[j].start.copy(),
                 svc.index.runs[j].pid.copy())
                for j in range(svc.index.k + 1)]
        backend.close()
        return a0, a1, io, runs, tracer

    a0_off, a1_off, io_off, runs_off, _ = _run(False, "off")
    a0_on, a1_on, io_on, runs_on, tracer = _run(True, "on")
    for off, on in ((a0_off, a0_on), (a1_off, a1_on)):
        for q, x, y in zip(queries, off, on):
            if isinstance(q, PointLookup):
                assert x == y
            else:
                np.testing.assert_array_equal(x, y)
    assert io_off == io_on, "quotient IOStats diverged under tracing"
    for (s0, p0), (s1, p1) in zip(runs_off, runs_on):
        np.testing.assert_array_equal(s0, s1)
        np.testing.assert_array_equal(p0, p1)
    for name in ("quotient.materialize", "quotient.level",
                 "quotient.patch", "quotient.query_wave"):
        assert tracer.find(name), f"no {name} spans"
    epochs = tracer.find_events("quotient.epoch")
    assert [e["attrs"]["epoch"] for e in epochs] == [1]
    _assert_no_aio_threads()


# ------------------------------------- host<->device boundary spans
def _within(tracer, rec, outer: str) -> bool:
    """`rec` ran inside a span named `outer` on the same thread."""
    return any(o["tid"] == rec["tid"] and o["depth"] < rec["depth"]
               and o["ts"] <= rec["ts"]
               and rec["ts"] + rec["dur"] <= o["ts"] + o["dur"]
               for o in tracer.find(outer))


@pytest.mark.parametrize("fused", [True, False])
def test_in_memory_build_upload_and_sync_spans(fused):
    """`build_bisim` names its upload and each device->host transfer as
    spans inside the caller's; outputs are bit-identical with tracing on
    and off, and the fused build records exactly one `build.sync`."""
    from repro.core import build_bisim
    g = gen.random_graph(500, 1500, 4, 3, seed=7)
    off = build_bisim(g, 6, fused=fused, sync_every=2)
    with tracing() as t:
        with t.span("test.build"):
            on = build_bisim(g, 6, fused=fused, sync_every=2)
    np.testing.assert_array_equal(off.pids, on.pids)
    assert off.counts == on.counts and off.converged_at == on.converged_at
    uploads = t.find("build.upload")
    assert len(uploads) == 1 and uploads[0]["parent"] == "test.build"
    assert uploads[0]["attrs"] == {"nodes": g.num_nodes,
                                   "edges": g.num_edges}
    syncs = t.find("build.sync")
    assert syncs and all(s["parent"] == "test.build" for s in syncs)
    assert not t.find_events("build.sync")      # spans, not events
    if fused:
        assert len(syncs) == 1
        assert syncs[0]["attrs"] == {"path": "fused", "what": "history",
                                     "iterations": len(on.pids) - 1}
    else:
        whats = [s["attrs"]["what"] for s in syncs]
        assert whats[0] == "count0" and whats[-1] == "history"
        assert set(whats[1:-1]) == {"drain"}
        assert all(s["attrs"]["path"] == "staged" for s in syncs)
    # the report lists the syncs as phases, with count and time
    rep = MetricsReport.from_tracer(t)
    assert rep.phases["build.sync"]["count"] == len(syncs)
    assert rep.phases["build.sync"]["total_s"] > 0
    assert "build.sync" not in rep.events and rep.events["build.dispatch"]
    assert "build.sync" in rep.format()


def _updates(m, rng, batches=4, size=12):
    n = m.backend.num_nodes
    for b in range(batches):
        src = rng.integers(0, n, size).astype(np.int32)
        dst = rng.integers(0, n, size).astype(np.int32)
        lab = rng.integers(0, 3, size).astype(np.int32)
        if b % 4 == 3:                   # delete edges that are there
            g = m.graph
            pick = rng.choice(g.num_edges, size, replace=False)
            src, lab, dst = g.src[pick], g.elabel[pick], g.dst[pick]
            m.delete_edges(src, lab, dst)
        else:
            m.add_edges(src, lab, dst)


@pytest.mark.parametrize("mode", MODES)
def test_device_maintenance_spans_and_bit_identity(mode):
    """Device maintenance under tracing: the same pids as untraced, each
    batch's edge rewrite in a `maint.apply_edges` span, and the host
    prep, the transfers and the store merges inside `maint.propagate`."""
    g = gen.random_graph(400, 1200, 4, 3, seed=5)

    def _run(traced):
        m = BisimMaintainer(g, 3, mode=mode, device=True)
        rng = np.random.default_rng(19)
        t = Tracer()
        if traced:
            with tracing(t):
                _updates(m, rng)
        else:
            _updates(m, rng)
        assert m.device
        return [p.copy() for p in m.pids], t

    pids_off, _ = _run(False)
    pids_on, t = _run(True)
    for a, b in zip(pids_off, pids_on):
        np.testing.assert_array_equal(a, b)
    applies = t.find("maint.apply_edges")
    assert [s["attrs"]["op"] for s in applies] == ["add"] * 3 + ["delete"]
    assert all(s["attrs"]["edges"] == 12 for s in applies)
    assert len(t.find("maint.propagate")) == len(applies)
    prepares = t.find("maint.prepare")
    assert prepares
    for s in prepares:
        assert set(s["attrs"]) == {"edges", "dedup"}
        assert s["attrs"]["dedup"] == (mode != "multiset")
        assert _within(t, s, "maint.propagate")
    syncs = t.find("maint.sync")
    assert syncs and not t.find_events("maint.sync")
    for s in syncs:
        assert s["attrs"]["what"] in {"levels_scalars", "level_scalar",
                                      "level_deltas", "probe"}
        assert _within(t, s, "maint.propagate")
    merges = t.find("store.merge_device")
    assert merges
    for s in merges:
        a = s["attrs"]
        assert a["minted"] > 0 and a["size"] <= a["capacity"]
        assert a["bucket"] >= a["minted"] and 1 <= a["level"] <= 3
        assert _within(t, s, "maint.propagate")
    # the merge dispatch is a span now; the other dispatches stay events
    assert not [e for e in t.find_events("maint.dispatch")
                if e["attrs"]["what"] == "merge_insert"]
    assert t.find_events("maint.dispatch")


def test_quotient_sync_and_expand_spans(tmp_path):
    """Each wave's mask fetch and expansion are spans inside its
    `quotient.query_wave`, with the fetch's bytes, the wave's query count
    and the answers' node count; answers match the untraced engine's."""
    from repro.quotient import (LabelPath, QuotientEngine, ReachTemplate,
                                materialize_quotient)
    g = gen.random_graph(300, 900, 3, 3, seed=4)
    m = BisimMaintainer(g, 3)
    index = materialize_quotient(g, m.backend, str(tmp_path / "q"),
                                 counts=[int(x) for x in m.next_pid])
    queries = [LabelPath((0,), level=1), LabelPath((1, 2), level=3),
               ReachTemplate((2,), src_label=0, level=2),
               LabelPath((0, 1), level=2), LabelPath((2,), level=1)]
    off = QuotientEngine(index, max_batch=2).query(queries)
    engine = QuotientEngine(index, max_batch=2)
    with tracing() as t:
        on = engine.query(queries)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    waves = t.find("quotient.query_wave")
    syncs, expands = t.find("quotient.sync"), t.find("quotient.expand")
    assert len(syncs) == len(expands) == len(waves) == engine.stats["waves"]
    for s in syncs + expands:
        assert s["parent"] == "quotient.query_wave"
    for w, s, e in zip(waves, syncs, expands):
        # the mask is bool [max_batch, blocks of the wave's level]
        assert s["attrs"]["bytes"] == 2 * index.counts[w["attrs"]["level"]]
        assert e["attrs"]["queries"] == w["attrs"]["batch"]
    assert sum(e["attrs"]["nodes"] for e in expands) == \
        sum(a.size for a in on)
