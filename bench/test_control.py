"""The controls come out as not correct through each cell's own check,
at a size a test run holds.  At full size the query control keys the
partition at 32 bits; a toy graph has too few blocks for 32-bit keys to
collide, so the test keys it at 12 bits, the same fault at the toy's
scale."""
import numpy as np
import pytest

from bench import control, harness
from bench.test_cells import toy

CELLS = [c["name"] for c in harness.load_spec()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2 ** 32 + 9])
def test_control_is_not_correct(workload, seed):
    out = control.read_control(workload, seed, steps=2,
                               overrides=toy(workload),
                               key_bits=12)
    assert not out["correct"], out
    assert out["failed"] > 0 and out["attempted"] > 0


def test_query_control_at_full_key_width_is_correct():
    # the same path through the check with no guarantee broken reads 0:
    # what fails the control is the 12-bit keys, not the plumbing
    out = control.read_control("linkedmdb.query", 3, steps=2,
                               overrides=toy("linkedmdb.query"),
                               key_bits=128)
    assert out["correct"], out
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_sound_reference_reads_zero():
    from bench import common, reference
    _, config, traffic = harness.load_cell(harness.load_spec(),
                                           "linkedmdb.build")
    config, traffic = harness.scaled(config, traffic,
                                     toy("linkedmdb.build"))
    g = common.make_driver(config, traffic, 5).make_graph()
    a = reference.bisim_levels(g, 10, early_stop=True)
    assert reference.history_mismatch(a, a) == 0
    # a history cut short counts the blocks of its missing levels
    assert reference.history_mismatch(a[:-1], a) == int(a[-1].max()) + 1
    # a node moved into another block
    moved = [lv.copy() for lv in a]
    moved[2][0] = moved[2][np.flatnonzero(moved[2] != moved[2][0])[0]]
    assert reference.history_mismatch(moved, a) > 0
