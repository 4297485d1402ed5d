"""The trace reduction on hand-built traces: busy union, idle share,
collective share and the naming of idle gaps."""
import pytest
from jax.profiler import ProfileData

import tracereduce as tr

MS = 1e-3


def op(a, b, name="fusion", collective=False):
    return tr.Op(a * MS, b * MS, name, collective)


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    trace = tr.Trace(
        window=(0.0, 10 * MS),
        ops={0: [op(-1, 1), op(2, 5), op(3, 4), op(4.5, 6), op(9, 12)]},
        spans=[])
    r = tr.reduce(trace)
    # [0,1] + [2,6] + [9,10] = 6 ms of 10
    assert r["window_s"] == pytest.approx(10 * MS)
    assert r["busy_s"] == [pytest.approx(6 * MS)]


def test_collective_time_and_ops_are_per_chip():
    trace = tr.Trace(
        window=(0.0, 10 * MS),
        ops={0: [op(0, 4, "scan"), op(4, 5, "all-gather.1", True)],
             1: [op(0, 2, "scan"), op(2, 5, "all-gather.1", True)]},
        spans=[])
    r = tr.reduce(trace)
    assert r["busy_s"] == [pytest.approx(5 * MS), pytest.approx(5 * MS)]
    assert r["collective_s"] == [pytest.approx(1 * MS), pytest.approx(3 * MS)]
    assert r["device_ops"][0] == ["scan", pytest.approx(6 * MS)]
    assert r["device_ops"][1] == ["all-gather.1", pytest.approx(4 * MS)]


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    trace = tr.Trace(
        window=(0.0, 10 * MS),
        ops={0: [op(1, 3), op(4, 8)]},
        spans=[(0.0, 3.2 * MS, "bench.search"),
               (3.2 * MS, 10 * MS, "bench.fetch")])
    r = tr.reduce(trace)
    # gaps [0,1] (search), [3,4] (mostly fetch) and [8,10] (fetch)
    named = {name: s for name, s in r["idle_gaps"]}
    assert named == {"bench.fetch x2": pytest.approx(3 * MS),
                     "bench.search x1": pytest.approx(1 * MS)}
    assert r["idle_gaps"][0][0] == "bench.fetch x2"


def test_gaps_with_no_span_are_unannotated():
    assert tr.gaps([(1.0, 2.0)], 0.0, 3.0) == [(0.0, 1.0), (2.0, 3.0)]
    r = tr.reduce(tr.Trace((0.0, 3.0), {0: [tr.Op(1.0, 2.0, "f", False)]}, []))
    assert r["idle_gaps"] == [["host.unannotated x2", 2.0]]


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[256,8635]{1,0:T(8,128)} fusion(f32[256,16]{1,0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce-start.3 = f32[] all-reduce-start(f32[] %x), replica_groups={{0,1,2,3}}" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 5000000000 }
    events { metadata_id: 3 offset_ps: 1000000000 duration_ps: 5000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.search" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(fused)" } }
}
"""


def test_parse_reads_device_ops_and_harness_spans_from_an_xspace():
    t = tr.parse(ProfileData.from_text_proto(XSPACE))
    # window 0..10 ms; ops at [1, 3] ms and [4, 5] ms; the module line is
    # not an op; host events other than the harness's spans are dropped
    assert t.window == (0.0, pytest.approx(10 * MS))
    assert [(o.name, o.collective) for o in t.ops[0]] == [
        ("fusion.1", False), ("all-reduce-start.3", True)]
    assert t.spans == [(pytest.approx(1 * MS), pytest.approx(6 * MS),
                        "bench.search")]
    r = tr.reduce(t)
    assert r["busy_s"] == [pytest.approx(3 * MS)]
    assert r["collective_s"] == [pytest.approx(1 * MS)]


def test_parse_refuses_a_trace_without_the_window_or_device_ops():
    with pytest.raises(ValueError, match="bench.window"):
        tr.parse(ProfileData.from_text_proto(
            XSPACE.replace('"bench.window"', '"other"')))
    with pytest.raises(ValueError, match="no device operations"):
        tr.parse(ProfileData.from_text_proto(
            XSPACE.replace("/device:TPU:0", "/device:CPU:0")))
