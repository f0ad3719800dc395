"""The trace reduction, on a trace built by hand and on a small trace
recorded on one v5e chip (`testdata/`)."""
import os

import pytest

from bench import trace

HAND = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1500000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 11000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__fused_build(3)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__hop(7)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "sort.2" } }
  event_metadata { key: 5 value { id: 5 name: "scatter.3" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
}
'''


@pytest.fixture
def hand_trace(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND))
    return str(path)


def test_hand_trace(hand_trace):
    red = trace.reduce_trace(
        hand_trace, extra_spans=lambda w0: [("maint.propagate", 500, 1500)])
    assert red.window == (0, 10000)
    assert red.chips == 1
    # busy: [2000, 5000] and [7000, 8000]; the op at 11000 is outside
    assert red.busy_s == pytest.approx(4e-6)
    assert red.window_s == pytest.approx(1e-5)
    assert red.idle_share() == pytest.approx(60.0)
    assert red.program_s == pytest.approx({"jit__fused_build": 4e-6,
                                           "jit__hop": 1e-6})
    assert red.program_seconds(r"_hop\b") == pytest.approx(1e-6)
    assert red.program_seconds(r"nothing") is None
    assert red.op_s == pytest.approx({"jit__fused_build/fusion.1": 2e-6,
                                      "jit__fused_build/sort.2": 2e-6,
                                      "jit__hop/scatter.3": 1e-6})
    # three 2-microsecond gaps, each named by the innermost open span
    assert sorted(red.gaps) == sorted([("maint.propagate", 2e-6),
                                       ("bench.step", 2e-6),
                                       ("bench.step", 2e-6)])
    bd = red.breakdown()
    assert bd["device_ops"][0][1] == pytest.approx(2e-6)
    assert len(bd["idle_gaps"]) == 3


def test_trace_without_window_is_refused(hand_trace):
    with pytest.raises(ValueError):
        trace.reduce_trace(hand_trace, window_name="bench.nothing")


SAMPLE = os.path.join(os.path.dirname(__file__), "testdata",
                      "query_window.xplane.pb")


def test_recorded_chip_trace():
    """A 12.95 s window of `linkedmdb.query` on one v5e (one call of 16
    queries), reduced to the numbers the harness reports."""
    red = trace.reduce_trace(SAMPLE)
    assert red.chips == 1
    assert red.window == (42173149.0, 12994174057.0)
    assert red.window_s == pytest.approx(12.952000908)
    assert red.busy_s == pytest.approx(11.196134479)
    assert red.idle_share() == pytest.approx(13.556719471162658)
    assert red.program_s == pytest.approx({"jit__hop": 11.195806974,
                                           "jit__init_mask": 0.00032965})
    ops = red.breakdown()["device_ops"]
    assert ops[0][0] == "jit__hop/fusion.1"
    assert ops[0][1] == pytest.approx(10.608865887)
    assert len(ops) == 10
    # every idle gap fell inside a call of the window
    assert red.gaps[0] == ("bench.step", pytest.approx(0.381964213))
    assert {name for name, _ in red.gaps} == {"bench.step"}
    # busy time never exceeds the window, nor the programs' own time
    assert red.busy_s <= red.window_s
    assert red.busy_s <= sum(red.program_s.values()) + 1e-9
