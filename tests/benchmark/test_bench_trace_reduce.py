"""The reduction from a profiler trace to numbers (benchmark/lib/trace_reduce.py)."""

import os

import numpy as np
import pytest

import bench_testlib
from benchmark.lib import trace_reduce as tr

# Two lines on one chip, stamps in ms: the core runs a fusion over
# [0, 4] and waits in all-reduce-done over [6, 8]; the asynchronous
# all-reduce is in flight over [3, 7].
TWO_LINES = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 2000000000 }
  }
  lines {
    name: "Async XLA Ops"
    events { metadata_id: 3 offset_ps: 3000000000 duration_ps: 4000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion(f32[8] %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce-done.1 = f32[8] all-reduce-done(%s)" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce-start.1 = f32[8] all-reduce-start(%g)" } }
}
planes {
  name: "/host:CPU"
  lines {
    name: "python"
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "benchmark_sync_marker" } }
  event_metadata { key: 2 value { id: 2 name: "$fit.py:1 loop" } }
}
"""


def _planes(text):
    from jax.profiler import ProfileData

    return tr.read_planes(ProfileData.from_text_proto(text))


def test_union_merges_overlapping_and_touching_intervals():
    got = tr.union(np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0]]))
    assert got.tolist() == [[0.0, 4.0], [5.0, 6.0]]
    assert tr.total(got) == 5.0
    assert tr.union(np.zeros((0, 2))).shape == (0, 2)


def test_subtract_and_gaps():
    a = np.array([[0.0, 10.0]])
    b = np.array([[1.0, 2.0], [4.0, 6.0], [9.0, 12.0]])
    assert tr.subtract(a, b).tolist() == [[0.0, 1.0], [2.0, 4.0], [6.0, 9.0]]
    assert tr.subtract(a, np.zeros((0, 2))).tolist() == [[0.0, 10.0]]
    assert tr.gaps(np.array([[0.0, 1.0], [3.0, 4.0]])).tolist() == [[1.0, 3.0]]


def test_self_times_take_nested_instructions_out_of_their_loop():
    # A while over [0, 10] whose body runs [1, 4] and [5, 9]; inside the
    # second, a nested call over [6, 7]; then an instruction of its own.
    spans = np.array([[0.0, 10.0], [1.0, 4.0], [5.0, 9.0], [6.0, 7.0], [11.0, 12.0]])
    assert tr.self_times(spans).tolist() == [3.0, 3.0, 3.0, 1.0, 1.0]
    assert tr.self_times(np.zeros((0, 2))).tolist() == []
    assert float(tr.self_times(spans).sum()) == tr.total(tr.union(spans))


def test_op_names():
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == "fusion.12"
    assert tr.op_kind("fusion.12") == "fusion"
    assert tr.op_kind("all-reduce-start.3.1") == "all-reduce-start"
    assert tr.COLLECTIVE.match("all-reduce-done.1")
    assert not tr.COLLECTIVE.match("fusion.1")


def test_hand_made_two_line_trace():
    planes = _planes(TWO_LINES)
    # Python tracer events ("$...") are not host spans; annotations are.
    assert [n for n, _, _ in planes["host"]] == ["benchmark_sync_marker"]
    s = tr.reduce_planes(planes, host_spans=[("train/data_wait", 3.5e-3, 6.5e-3)])
    assert s["chips"] == 1
    assert s["window_s"] == pytest.approx(8e-3)
    assert s["busy_s"] == pytest.approx(6e-3)  # 4 ms fusion + 2 ms done
    assert 1.0 - s["busy_s"] / s["window_s"] == pytest.approx(0.25)
    assert s["collective_s"] == pytest.approx(5e-3)  # [3, 8]
    assert s["collective_exposed_s"] == pytest.approx(4e-3)  # [4, 8]
    assert s["collective_ops"] == 1
    assert s["device_ops"][0] == ["fusion", pytest.approx(4e-3)]
    assert s["idle_gaps"] == [["train/data_wait", pytest.approx(2e-3)]]
    # The marker sits at 1 ms on the trace's clock.
    assert tr.marker_start_s(planes, "benchmark_sync_marker") == pytest.approx(1e-3)
    assert tr.marker_start_s(planes, "absent") is None
    # From 5 ms on, only the all-reduce-done is left.
    late = tr.reduce_planes(planes, start_s=5e-3)
    assert late["busy_s"] == pytest.approx(2e-3) and late["collective_ops"] == 1


def test_short_gaps_are_not_attributed():
    assert tr.attribute_gap((0.0, 1e-3), [("span", 0.0, 1.0)]) == "unattributed"
    assert tr.attribute_gap((0.0, 5e-3), [("span", 0.0, 1.0)]) == "span"
    assert tr.attribute_gap((0.0, 5e-3), []) == "unattributed"


def test_no_device_event_reduces_to_none():
    assert tr.reduce_planes({"devices": {}, "host": []}) is None


def test_recorded_v5e_trace():
    """A toy program traced on a TPU v5e (benchmark/tools/record_trace.py,
    PR 22): four executions of one jitted step, a 5 ms sleep after the
    second."""
    from jax.profiler import ProfileData

    path = os.path.join(bench_testlib.DATA, "toy_v5e.xplane.pb")
    planes = tr.read_planes(ProfileData.from_file(path))
    assert sorted(planes["devices"]) == [0]
    s = tr.reduce_planes(planes)
    assert s["modules"]["jit_step"][0] == 4
    assert s["busy_s"] == pytest.approx(14.52e-6, rel=1e-2)
    assert s["window_s"] == pytest.approx(6.724e-3, rel=1e-2)
    assert s["idle_gaps"][0][1] == pytest.approx(6.138e-3, rel=1e-2)
    assert s["collective_ops"] == 0 and s["collective_s"] == 0.0
    assert s["device_ops"][0][0] == "convert_reduce_fusion"
    assert any(n == "bench_sync_marker" for n, _, _ in planes["host"])
