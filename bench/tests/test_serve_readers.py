"""The readers of the serve engine's spans and program names
(``step_host_ms``, ``prefill_ms``, ``merge_ms``), on a synthetic
reduction and synthetic counters: each reads what it names, and reads
nothing (``None``) where a program without those spans and names
leaves it nothing to read."""

import pytest

from bench import harness, trace_reduce
from checkout import BENCH

METRICS = BENCH / "metrics"


def _read(name, **ctx):
    return harness.reader(METRICS, name)(ctx)


def _red(modules):
    return trace_reduce.Reduction(6.0, 5.0, 1, modules, {}, [])


def test_step_host_ms_leaves_out_the_waits_on_the_device():
    stats = {"step_s": 2.0, "step_n": 40, "sync_s": 0.9, "sync_n": 38,
             "first_token_s": 0.3, "first_token_n": 5, "steps": 40}
    assert _read("step_host_ms", stats=stats) == pytest.approx(
        (2.0 - 0.9 - 0.3) / 40 * 1e3)


@pytest.mark.parametrize("stats", [
    {},                                             # no steps in the window
    {"step_s": 0.0, "step_n": 0, "sync_s": 0.0, "first_token_s": 0.0},
    # a program without the spans: only the old counters
    {"steps": 40, "prefill_s": 1.0, "admitted": 3, "decode_s": 2.0},
])
def test_step_host_ms_reads_nothing_without_steps_or_spans(stats):
    assert _read("step_host_ms", stats=stats) is None


@pytest.mark.parametrize("metric,program", [("prefill_ms", "jit_prefill"),
                                            ("merge_ms", "jit_merge_slot")])
def test_program_readers_take_device_ms_per_execution(metric, program):
    red = _red({f"{program}(12)": [3, 0.030], f"{program}(40)": [1, 0.010],
                "jit_decode_step(7)": [9, 1.0]})
    assert _read(metric, trace=red) == pytest.approx(0.040 / 4 * 1e3)


@pytest.mark.parametrize("metric", ["prefill_ms", "merge_ms"])
def test_program_readers_read_nothing_without_their_program(metric):
    # the parent's anonymous prefill program and eager merges
    red = _red({"jit__lambda(3)": [5, 0.1], "jit_decode_step(7)": [9, 1.0]})
    assert _read(metric, trace=red) is None
    assert _read(metric, trace=None) is None


def test_decode_readers_cost_each_step_through_the_architecture():
    import json

    from bench import cost, peaks
    from bench.arch import dense
    from checkout import DATA

    cj = json.loads((DATA / "tiny.json").read_text())
    pk = peaks.peaks_for("TPU v5 lite")
    red = _red({"jit_decode_step(7)": [2, 0.004]})
    red.host_s = 0.5
    # an admitting step with no decoding lane is left out
    red.steps = [(1.0, (20, 20, 10)), (1.1, ()), (1.2, (21, 21))]
    ctx = {"trace": red, "peaks": pk, "arch": dense, "cj": cj}
    steps = [dense.decode_step(cj, lens) for lens in ((20, 20, 10), (21, 21))]
    least = sum(cost.least_time(f, b, pk)[0] for f, b in steps) / 2
    assert _read("decode_roofline", **ctx) == pytest.approx(
        least / 0.002 * 100.0, rel=1e-12)
    assert _read("mfu.decode", **ctx) == pytest.approx(
        sum(f for f, _ in steps) / (0.5 * 197e12) * 100.0, rel=1e-12)
