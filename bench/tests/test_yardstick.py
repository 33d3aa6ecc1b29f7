"""The yardstick's pieces on their own: the cost of a decode step by
hand, the peak table, the traffic generator's fixed work per seed, the
percentile arithmetic and the layout check of the weights."""

import json

import numpy as np
import pytest

from bench import cost, loadgen, pct, peaks, weights
from bench.arch import dense
from checkout import DATA


def _tiny(**kw):
    cj = json.loads((DATA / "tiny.json").read_text())
    cj.update(kw)
    return cj


def test_decode_step_cost_bf16_by_hand():
    # d 64, 4 heads of 16, 2 kv heads, d_ff 128, 2 layers, vocab 256,
    # tied head, q/k/v biases
    cj = _tiny()
    per_layer = (64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64
                 + 3 * 64 * 128)                       # 36864
    assert dense.matmul_params(dense.Shape.from_config(cj)) \
        == 2 * per_layer + 64 * 256 == 90112
    # three lanes attending over 50 live keys in all
    flops, nbytes = dense.decode_step(cj, [20, 20, 10])
    assert flops == 2 * 90112 * 3 + 4 * 2 * 4 * 16 * 50 == 566272
    weights_b = 2 * (2 * per_layer + 2 * 4 * 64 + 2 * 8 * 16) + 4 * 64 \
        + 2 * 64 * 256                                 # 182016
    kv = 2 * 2 * 2 * 16 * 2 * (50 + 3)                 # 13568
    logits = 2 * 256 * 3
    assert nbytes == weights_b + kv + logits == 197120


def test_decode_step_cost_w4_by_hand():
    cj = _tiny(tie_word_embeddings=False, attention_bias=False,
               serve={"weights": "w4"})
    # half a byte a weight plus an f32 scale per entry of the last axis
    layer = ((64 * 64 // 2 + 4 * 16) + 2 * (64 * 32 // 2 + 4 * 16)
             + (64 * 64 // 2 + 4 * 64) + 2 * (64 * 128 // 2 + 4 * 128)
             + (128 * 64 // 2 + 4 * 64) + 4 * 2 * 64)  # 20672
    head = 64 * 256 // 2 + 4 * 256
    rows = 3 * 64 // 2 + 4 * 64
    assert dense.weight_bytes(dense.Shape.from_config(cj), 3) \
        == 2 * layer + 4 * 64 + head + rows == 51168
    kv = 2 * 2 * 2 * 16 * 2 * (50 + 3)
    logits = 2 * 256 * 3
    assert dense.decode_step(cj, [20, 20, 10])[1] == 51168 + kv + logits


@pytest.mark.parametrize("lens", [[48, 1, 1], [17, 17, 16], [1, 1, 48]])
def test_decode_step_cost_splits_live_keys_over_lanes_alike(lens):
    # the same 50 live keys over the same three lanes cost the same
    cj = _tiny()
    assert dense.decode_step(cj, lens) == dense.decode_step(cj, [20, 20, 10])


def test_least_time_names_its_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = cost.least_time(197e12, 1.0, pk)
    assert (t, bound) == (1.0, "compute")
    t, bound = cost.least_time(1.0, 819e9, pk)
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_every_seed_gets_the_same_work():
    mix = json.loads((DATA / "tiny_open.json").read_text())
    a = loadgen.open_loop(mix, 10.0, 1, 256)
    b = loadgen.open_loop(mix, 10.0, 2**33 + 1, 256)
    assert len(a) == len(b) == 160
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
    gaps = [np.sort(np.diff([r.due for r in x])) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-12)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert max(r.due for r in a) < 10.0
    assert all(8 <= len(r.prompt) <= 80 for r in a)


def test_bursts_are_due_together():
    mix = json.loads((DATA / "tiny_open.json").read_text())
    mix["arrival"] = {"kind": "burst", "size": 4, "rate_per_s": 8.0}
    reqs = loadgen.open_loop(mix, 10.0, 3, 256)
    dues = [r.due for r in reqs]
    assert len(reqs) == 80 and len(set(dues)) == 20
    assert all(dues.count(d) == 4 for d in set(dues))


def test_backlog_staggers_the_first_lanes():
    mix = json.loads((DATA / "tiny_closed.json").read_text())
    bl = loadgen.Backlog(mix, 5, 256, slots=4)
    first = [bl.take() for _ in range(4)]
    [bl.take() for _ in range(60)]
    cycle = [bl.take() for _ in range(64)]    # the list again, reordered
    assert len({r.max_new for r in first}) > 1
    assert sorted(r.max_new for r in cycle) == sorted(
        loadgen.lengths(mix["output_len"], 64))


@pytest.mark.parametrize("seed", [0, 2**33 + 7])
def test_backlog_prefixes_ask_the_same_work_every_seed(seed):
    mix = json.loads((DATA / "tiny_closed.json").read_text())
    n = mix["list_size"]
    order = loadgen.stratified_order(n, np.random.default_rng(seed))
    assert sorted(order) == list(range(n))
    for k in (8, 16, 32):
        assert sorted(order[:k] // (n // k)) == list(range(k))
    # requests 0-15 and 16-31 each hold one length of every sixteenth
    # of the list (the first four outputs are cut to their residuals)
    bl = loadgen.Backlog(mix, seed, 256, slots=4)
    reqs = [bl.take() for _ in range(32)]
    for dist, got in (("prompt_len", [len(r.prompt) for r in reqs[:16]]),
                      ("output_len", [r.max_new for r in reqs[16:]])):
        q = np.sort(loadgen.lengths(mix[dist], n)).reshape(16, -1)
        assert all(lo <= x <= hi for x, lo, hi
                   in zip(sorted(got), q[:, 0], q[:, -1]))


def test_percentile_is_linear_between_order_statistics():
    assert pct.pct([1, 2, 3, 4], 50) == 2.5
    assert pct.pct(np.arange(101), 95) == 95.0


def test_weight_layout_matches_the_serving_stack():
    import jax

    from repro.models.model import LM

    for name in ("tiny", "tiny-w4"):
        cj = json.loads((DATA / f"{name}.json").read_text())
        model = LM(dense.model_config(cj))
        weights.check_layout(jax.eval_shape(model.init, weights.seed_key(0)),
                             dense.layout(cj))
    bad = dict(dense.layout(cj))
    bad.pop(("head",))
    with pytest.raises(ValueError, match="missing"):
        weights.check_layout(jax.eval_shape(model.init, weights.seed_key(0)),
                             bad)
