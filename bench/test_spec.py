"""BENCHMARK.json against the rules its harness and its checker rely on."""
import json
import os
import re

import pytest

from bench import harness

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
E2E = {m["name"]: m for m in SPEC["end_to_end"]}
CELLS = {c["name"]: c for c in SPEC["workloads"]}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p for p in SPEC["paths"])
    assert 1 <= len(SPEC["command"]) <= 32
    assert not any(w.startswith("/") or ".." in w for w in SPEC["command"])
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_check_budget_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_unique_and_plain(kind):
    names = [e["name"] for e in SPEC[kind]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric in SPEC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert 1 <= len(metric["layer"]) <= 200
        moved = E2E[metric["moves"]]
        # every cell the metric is read in reports the metric it moves
        for cell in metric.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS)
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
        reader = os.path.join(harness.BENCH, "metrics",
                              metric["name"] + ".py")
        assert os.path.exists(reader)
        assert callable(harness.load_reader(metric["name"]))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])
    _, config, traffic = harness.load_cell(SPEC, cell["name"])
    # every piece is a file found by its name
    from bench import common
    driver = common.load_module("drivers", traffic["driver"])
    assert callable(driver.Driver) and callable(driver.control)
    assert callable(common.load_module("generators",
                                       config["generator"]).draw)
    assert int(traffic["k"]) >= 1 and traffic["limits"]
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    e2e = [m["name"] for m in harness.metrics_for(SPEC, cell["name"],
                                                  "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(SPEC, cell["name"], "per_layer")


def test_pairs_of_config_and_traffic_are_unique():
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(c["chips"] == 4 for c in SPEC["workloads"])
    assert four <= max(1, len(pairs) // 2)


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"].startswith("bench/")
    assert any(c["config"] == conf["name"] for c in SPEC["workloads"])
    with open(os.path.join(harness.ROOT, conf["file"])) as f:
        body = json.load(f)
    assert body["name"] == conf["name"]
    assert body["source"] == conf["source"]
    assert body["reduced"] == conf["reduced"]
    assert len(conf["reduced"]) <= 16
    assert all(NAME.match(k) for k in conf["reduced"])
    # a reduction cuts scale, never a width
    assert not any(k.endswith(("_dim", "_rank")) for k in conf["reduced"])
    assert body["guarantees"]


def test_setup_bound_and_peaks():
    assert E2E["setup_s"]["bound"] <= 0.25
    with open(os.path.join(harness.BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"]
    assert peaks["kinds"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("not a chip")
