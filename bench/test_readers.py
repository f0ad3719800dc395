"""The readers of the host-boundary spans (`build.upload`, `*.sync`,
`maint.apply_edges`, `maint.prepare`, `store.merge_device`,
`quotient.expand`): each on a `RunRecord` built by hand, on one built as
a program without those spans records it, and in a traced toy run of
its cell."""
import statistics

import pytest

from bench import harness
from bench.test_cells import run_toy

NEW = {
    "linkedmdb.build": ["build.upload_ms", "build.sync_ms"],
    "linkedmdb.maintain": ["maint.apply_ms", "maint.prepare_ms",
                           "maint.sync_ms", "maint.merge_rows"],
    "linkedmdb.query": ["quotient.sync_ms", "quotient.expand_ms"],
}


def _span(name, ms, **attrs):
    return {"name": name, "dur": int(ms * 1e6), "attrs": attrs}


def _record(spans, **work):
    return harness.RunRecord(0, work, {}, None, spans)


def _read(metric, record):
    return harness.load_reader(metric)(record)


def _batches(n):
    return [_span("maint.propagate", 100.0) for _ in range(n)]


BUILD = [_span("build.upload", 3.0), _span("build.sync", 2900.0),
         _span("build.upload", 5.0), _span("build.sync", 2880.0)]
MAINT = (_batches(4)
         + [_span("maint.apply_edges", 1000.0, op="add", edges=1024)] * 4
         + [_span("maint.prepare", 30.0, edges=9000, dedup=True)] * 10
         + [_span("maint.sync", 250.0, what="level_scalar")] * 8
         + [_span("store.merge_device", 0.1, capacity=1 << 22),
            _span("store.merge_device", 0.1, capacity=1 << 20)])
QUERY = ([_span("quotient.query_wave", 1200.0)] * 24
         + [_span("quotient.sync", 1000.0, bytes=1 << 20)] * 24
         + [_span("quotient.expand", 150.0, queries=1, nodes=10)] * 24)

CASES = [
    ("build.upload_ms", BUILD, {"builds": 2}, 4.0),
    ("build.sync_ms", BUILD, {"builds": 2}, 2890.0),
    ("maint.apply_ms", MAINT, {"batches": 4}, 1000.0),
    ("maint.prepare_ms", MAINT, {"batches": 4}, 75.0),
    ("maint.sync_ms", MAINT, {"batches": 4}, 500.0),
    ("maint.merge_rows", MAINT, {"batches": 4},
     ((1 << 22) + (1 << 20)) / 4),
    ("quotient.sync_ms", QUERY, {"calls": 2}, 12000.0),
    ("quotient.expand_ms", QUERY, {"calls": 2}, 1800.0),
]


@pytest.mark.parametrize("metric,spans,work,want", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_on_hand_built_record(metric, spans, work, want):
    assert _read(metric, _record(spans, **work)) == pytest.approx(want)


@pytest.mark.parametrize("metric,spans,work", [c[:3] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_reader_without_its_spans_reads_nothing(metric, spans, work):
    """A program that has none of these spans (its syncs instant events,
    its merges dispatch events), as the earlier program is, gives no
    value, and nothing raises; so does a window with no work."""
    earlier = [s for s in spans if s["name"] in
               ("maint.propagate", "quotient.query_wave")]
    assert _read(metric, _record(earlier, **work)) is None
    assert _read(metric, _record([], **work)) is None
    no_work = [s for s in spans if s["name"] != "maint.propagate"]
    assert _read(metric, _record(no_work, **{k: 0 for k in work})) is None


@pytest.mark.parametrize("metric", ["maint.prepare_ms", "maint.sync_ms",
                                    "maint.merge_rows"])
def test_maintenance_reader_reads_zero_where_batches_did_none(metric):
    """Batches that ran without preparing, syncing or merging read 0,
    not nothing."""
    spans = _batches(2) + [_span("maint.apply_edges", 900.0)] * 2
    assert _read(metric, _record(spans, batches=2)) == 0.0


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_toy_run_reports_the_new_metrics(workload):
    result, lines = run_toy(workload, trace=True)
    assert result["correct"], result["checks"]
    got = result["metrics"]
    for name in NEW[workload]:
        assert name in got, (name, sorted(got))
        assert got[name]["value"] >= 0
    spec = {m["name"]: m for m in harness.load_spec()["per_layer"]}
    assert all(got[n]["unit"] == spec[n]["unit"] for n in NEW[workload])
    window = lines[1]
    value = {n: got[n]["value"] for n in NEW[workload]}
    if workload == "linkedmdb.maintain":
        # the spans are disjoint parts of a batch
        per_batch = (value["maint.apply_ms"] + value["maint.prepare_ms"]
                     + value["maint.sync_ms"])
        assert per_batch <= 1000.0 * statistics.mean(window["batch_s"])
        assert got["maint.propagate_ms"]["value"] > 0
    elif workload == "linkedmdb.query":
        # ... and parts of the call's waves
        from bench.test_cells import _toy_driver
        d = _toy_driver(workload, 7)
        waves = len({(lev, hops) for kind, hops, lev in d.shapes
                     if kind != "PointLookup"})
        assert value["quotient.sync_ms"] + value["quotient.expand_ms"] \
            <= got["quotient.wave_ms"]["value"] * waves
    else:
        assert value["build.sync_ms"] > 0
        assert value["build.upload_ms"] + value["build.sync_ms"] <= \
            1000.0 * window["seconds"] / window["builds"]
