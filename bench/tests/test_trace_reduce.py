"""The reduction from a profiler trace to busy time, per-program device
time and named idle gaps: on synthetic intervals, and on a trace
recorded on a TPU v5e by a traced run of the tiny closed-loop cell
(``data/tiny_trace.xplane.pb``)."""

import pytest

from bench import trace_reduce
from checkout import DATA

TRACE = DATA / "tiny_trace.xplane.pb"


def test_union_merges_overlaps_and_returns_gaps():
    busy, gaps = trace_reduce._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == 3 + 4
    assert gaps == [(3, 5)]
    assert trace_reduce._union([]) == (0.0, [])


def test_host_activity_names_the_innermost_event_in_a_bench_span():
    host = [(0, 100, "bench.window"), (10, 50, "bench.step"),
            (20, 30, "PjitFunction(decode_step)"), (60, 90, "bench.sleep")]
    assert trace_reduce._host_activity(host, 25) == \
        "bench.step/PjitFunction(decode_step)"
    assert trace_reduce._host_activity(host, 40) == "bench.step"
    assert trace_reduce._host_activity(host, 95) == "host idle"


def test_program_names_drop_their_id():
    red = trace_reduce.Reduction(1.0, 0.5, 1, {"jit_decode_step(12)": [3, 0.3],
                                               "jit_decode_step(40)": [1, 0.1],
                                               "jit__lambda(7)": [2, 0.2]},
                                 {}, [])
    assert red.module("jit_decode_step") == (4, pytest.approx(0.4))


def test_recorded_tpu_trace():
    red = trace_reduce.reduce_file(str(TRACE))
    assert red.chips == 1
    assert 0 < red.busy_s < red.window_s
    n, s = red.module("jit_decode_step")
    assert n > 0 and 0 < s < red.busy_s
    assert red.gaps and all(sec > 0 for _, sec in red.gaps)
    assert red.gaps == sorted(red.gaps, key=lambda g: -g[1])
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
