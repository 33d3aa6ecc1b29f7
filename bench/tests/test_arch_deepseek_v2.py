"""The DeepSeek-V2 architecture module (``bench/arch/deepseek_v2.py``):
its layout is the serving stack's own, its leaf-at-a-time weights equal
a one-shot build, a whole closed-loop cell on the CPU agrees with its
reference while the fp8 control does not, its cost of a decode step
matches a hand count at the published widths, and it refuses settings
it does not build."""

import hashlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.arch import deepseek_v2

import checkout
from checkout import BENCH, DATA

SEED = 2**33 + 5


def _digest(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[name] = hashlib.sha256(str((a.dtype.str, a.shape)).encode()
                                   + a.tobytes()).hexdigest()[:16]
    return out


def _config(name):
    return json.loads((DATA / f"{name}.json").read_text())


def test_deepseek_layout_is_the_stacks_own():
    from repro.models.model import LM

    cj = _config("tiny-dsv2")
    model = LM(deepseek_v2.model_config(cj))
    weights.check_layout(jax.eval_shape(model.init, weights.seed_key(SEED)),
                         deepseek_v2.layout(cj))


def test_deepseek_leaf_at_a_time_equals_one_shot_build():
    """``program_weights`` draws and packs a leaf and a layer at a time;
    drawing every layer at once and quantizing the whole tree with
    ``qweight.quantize_tree`` gives the same bits."""
    from repro.models import qweight

    cj = _config("tiny-dsv2")
    key = weights.seed_key(SEED)
    L = cj["num_hidden_layers"] - cj["first_k_dense_replace"]

    def one_shot(k):
        tree = {}
        for i, path, shape, dtype, stacked in deepseek_v2._leaves(cj):
            kk = jax.random.fold_in(k, i)
            leaf = (jnp.stack([deepseek_v2._draw(
                cj, path, shape, dtype, jax.random.fold_in(kk, l))
                for l in range(L)]) if stacked
                else deepseek_v2._draw(cj, path, shape, dtype, kk))
            deepseek_v2._set(tree, path, leaf)
        return qweight.quantize_tree(deepseek_v2._lists(tree), bits=4,
                                     names=set(cj["serve"]["w4_leaves"]))

    got = _digest(deepseek_v2.program_weights(cj, key))
    assert got == _digest(jax.jit(one_shot)(key))
    assert "unit/b0/moe/w_gate/0" in got and "lead/0/mlp/w_up/1" in got


def test_deepseek_stack_agrees_with_the_reference_and_fp8_does_not(tmp_path):
    """A whole closed-loop cell on the CPU: prefill, then decode through
    the latent cache, checked at logits against ``logits_rows`` (f32).
    Its limit, 0.5, lies above the program's widest gap on the tiny
    config (0.05-0.28 over three seeds: bf16 activations, and a routing
    choice that rounding can flip) and under the fp8 control's narrowest
    (0.94), which must fail it."""
    spec = checkout.make(tmp_path)
    (tmp_path / "bench" / "arch" / "deepseek_v2.py").symlink_to(
        BENCH / "arch" / "deepseek_v2.py")
    (tmp_path / "bench" / "configs" / "tiny-dsv2.json").write_text(
        (DATA / "tiny-dsv2.json").read_text())
    (tmp_path / "bench" / "limits" / "tiny-dsv2.closed.json").write_text(
        (DATA / "limits-dsv2.json").read_text())
    spec["configs"].append({"name": "tiny-dsv2",
                            "file": "bench/configs/tiny-dsv2.json"})
    spec["workloads"].append({"name": "tiny-dsv2.closed",
                              "config": "tiny-dsv2", "traffic": "closed",
                              "chips": 1})
    r = harness.run(tmp_path, spec, "tiny-dsv2.closed", 2**31 + 3, 3.0,
                    False, time.perf_counter(), require_chip=False,
                    controls=("fp8",))
    assert r["correct"], r["checks"]
    assert r["checks"]["checked_tokens"]["value"] >= 32
    assert not r["control"]["fp8"]["correct"], r["control"]


def test_deepseek_decode_step_cost_by_hand():
    # published widths, 32 lanes attending over 300 live keys each
    cj = json.loads((BENCH / "configs" / "deepseek-v2-lite-w4.json")
                    .read_text())
    lanes, keys = 32, 32 * 300
    attn = (2048 * 16 * 192 + 2048 * 576 + 2 * 512 * 16 * 128
            + 16 * 128 * 2048)                          # 13,762,560
    dense = 3 * 2048 * 10944                            # 67,239,936
    moe = 6 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64   # 69,337,088
    params = 27 * attn + dense + 26 * moe + 2048 * 102400
    assert params == 2_451_308_544
    latent = 2 * 16 * (512 + 64) + 2 * 16 * 512         # per key, layer
    flops, nbytes = deepseek_v2.decode_step(cj, [300] * lanes)
    assert flops == 2 * params * lanes + 27 * latent * keys \
        == 165_908_054_016
    # w4: half a byte a weight and a 4-byte scale per output channel
    attn_b = ((32768 * 192 // 2 + 4 * 192) + (2048 * 576 // 2 + 4 * 576)
              + 2 * (8192 * 128 // 2 + 4 * 128)
              + (2048 * 2048 // 2 + 4 * 2048)
              + 4 * (2 * 2048 + 512))                   # 6,912,000
    dense_b = 2 * (2048 * 10944 // 2 + 4 * 10944) \
        + (10944 * 2048 // 2 + 4 * 2048)                # 33,715,712
    expert_b = 2 * (2048 * 1408 // 2 + 4 * 1408) \
        + (1408 * 2048 // 2 + 4 * 2048)                 # 4,344,832
    shared_b = 2 * (2048 * 2816 // 2 + 4 * 2816) \
        + (2816 * 2048 // 2 + 4 * 2048)                 # 8,681,472
    reached = 64 * (1 - (1 - 6 / 64) ** 32)             # 61.26 of 64
    assert abs(reached - 61.258) < 1e-3
    moe_b = reached * expert_b + shared_b + 4 * 2048 * 64
    weights_b = (27 * attn_b + dense_b + 26 * moe_b + 4 * 2048
                 + (2048 * 102400 // 2 + 4 * 102400)    # the whole head
                 + (32 * 2048 // 2 + 4 * 2048))         # the tokens' rows
    cache = 27 * (512 + 64) * 2 * (keys + lanes)        # read and write
    logits = 2 * 102400 * lanes
    assert nbytes == pytest.approx(weights_b + cache + logits, rel=1e-12)
    assert 7.7e9 < nbytes < 7.9e9


@pytest.mark.parametrize("keys", [{"q_lora_rank": 1536},
                                  {"topk_method": "group_limited_greedy"},
                                  {"scoring_func": "sigmoid"},
                                  {"tie_word_embeddings": True}])
def test_deepseek_refuses_what_it_does_not_build(keys):
    with pytest.raises(ValueError, match="does not build"):
        deepseek_v2.model_config(dict(_config("tiny-dsv2"), **keys))
