"""Each cell through the harness internals at toy size on the CPU: set-up,
a short window and the check, with the look for a chip left out."""
import io
import json
import time

import numpy as np
import pytest

from bench import harness

# each configuration's parameters, and each traffic's, at toy size
TOY = {
    "linkedmdb": {"num_nodes": 6000, "num_edges": 16000,
                  "distinct_edges": 15000},
}
TOY_TRAFFIC = {
    "maintain": {"warmup": [["add", 1024], ["delete", 1024]]},
}


def toy(workload):
    """The `overrides` that cut a cell to toy size."""
    cell = harness.find_cell(harness.load_spec(), workload)
    return {"params": TOY[cell["config"]],
            "traffic": TOY_TRAFFIC.get(cell["traffic"], {})}
STAND_IN = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
CELLS = [c["name"] for c in harness.load_spec()["workloads"]]


def run_toy(workload, seed=2 ** 31 + 77, seconds=0.3, trace=False):
    out = io.StringIO()
    result = harness.run(workload, seed, seconds, trace,
                         t_start=time.perf_counter(), device=STAND_IN,
                         overrides=toy(workload), compile_cache=False,
                         out=out)
    return result, [json.loads(x) for x in out.getvalue().splitlines()]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    result, lines = run_toy(workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    spec = harness.load_spec()
    want = {m["name"] for m in harness.metrics_for(spec, workload,
                                                   "end_to_end")}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert [x["phase"] for x in lines] == ["setup", "window", "check"]
    assert "window_compiles" in lines[1]
    # the checks come last in the result line, each with its limit
    assert list(result)[-1] == "checks"
    assert all(set(c) == {"value", "limit"}
               for c in result["checks"].values())


@pytest.mark.parametrize("workload", ["linkedmdb.maintain",
                                      "linkedmdb.query"])
def test_traced_run_reads_program_spans(workload):
    result, _ = run_toy(workload, trace=True)
    assert result["correct"]
    assert "busy_s" in result["device"] and "window_s" in result["device"]
    # the CPU has no TPU plane: only span and counter metrics are read,
    # and the device metrics are left out rather than given as 0
    names = set(result["metrics"])
    assert names and not any("roofline" in n or "idle" in n for n in names)
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def _toy_driver(workload, seed):
    from bench import common
    spec = harness.load_spec()
    _, config, traffic = harness.load_cell(spec, workload)
    config, traffic = harness.scaled(config, traffic, toy(workload))
    return common.make_driver(config, traffic, seed)


def test_same_seed_same_traffic():
    from bench import common
    maintain = common.load_module("drivers", "maintain")
    batches = []
    for _ in range(2):
        d = _toy_driver("linkedmdb.maintain", 12345678901)
        book = maintain.EdgeBook(d.make_graph(), d.law)
        batches.append([k.tolist() for _, k in
                        maintain.replay(book, d.traffic, 12345678901, 5)])
    assert batches[0] == batches[1]


def test_query_shapes_do_not_depend_on_the_seed():
    pools = []
    for seed in (1, 2 ** 33 + 5):
        d = _toy_driver("linkedmdb.query", seed)
        d.g = d.make_graph()
        pools.append([(type(q).__name__, q.level,
                       len(getattr(q, "labels", ()))) for q in
                      d._make_pool()])
    assert pools[0] == pools[1]


def test_query_call_covers_every_level_and_hop_count():
    d = _toy_driver("linkedmdb.query", 7)
    shapes = d.shapes
    max_hops = max(h for _, h, _ in shapes)
    paths = {(h, j) for kind, h, j in shapes if kind != "PointLookup"}
    assert paths == {(h, j) for h in range(1, max_hops + 1)
                     for j in range(h, d.k + 1)}
    points = sorted(j for kind, _, j in shapes if kind == "PointLookup")
    assert points == list(range(d.k + 1))
    kinds = [kind for kind, _, _ in shapes]
    assert len({kinds.count(k) for k in set(kinds)}) == 1   # equal shares


@pytest.mark.parametrize("config", sorted(TOY))
def test_every_seed_gives_the_same_shapes(config):
    from bench import common, graphs
    conf = harness.load_json(harness.BENCH, "configs", config + ".json")
    law = graphs.EdgeLaw(common.load_module("generators", conf["generator"]),
                         dict(conf["params"], **TOY[config]))
    sizes = {law.graph(np.random.default_rng(seed)).num_edges
             for seed in (0, 1, 2 ** 31 + 3, 2 ** 40 + 1)}
    assert sizes == {TOY[config]["distinct_edges"]}
