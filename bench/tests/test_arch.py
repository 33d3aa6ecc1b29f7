"""The architecture modules behind the harness: the dense module makes
the weights and reference logits that the benchmark made before it had
modules (each leaf's digest pinned), refuses configurations it does not
build, an unknown architecture raises, and a module that exists only
in a checkout runs a whole cell there."""

import hashlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, harness, weights
from bench.arch import dense

import checkout
from checkout import BENCH, DATA

SEED = 2**33 + 5

# sha256 of (dtype, shape) and the bytes of each leaf, first 16 hex
# digits, as the benchmark made them before the architecture modules
PROGRAM = {
    "tiny": {
        "embed": "a0afef5db6b38cf8",
        "final_norm": "7b57867185d03d82",
        "unit/b0/attn/bk": "111de3bc9bfd309f",
        "unit/b0/attn/bq": "9d911ae4736fe764",
        "unit/b0/attn/bv": "326cf38093ed2bbe",
        "unit/b0/attn/wk": "d60f1b2e434ccfa4",
        "unit/b0/attn/wo": "4fb105fb67f16b77",
        "unit/b0/attn/wq": "2317929f19368a37",
        "unit/b0/attn/wv": "ca988e0420514b1d",
        "unit/b0/ln1": "3aa5e846a7e6e05c",
        "unit/b0/ln2": "1bd57a4d112e2505",
        "unit/b0/mlp/w_down": "2713031dada46b9b",
        "unit/b0/mlp/w_gate": "536a42431c1683fb",
        "unit/b0/mlp/w_up": "3b2a7398ed63213b",
    },
    "tiny-w4": {                 # bit-planes (0) and scales (1)
        "embed/0": "26a3cbbf346e07cb",
        "embed/1": "a68989869db68725",
        "final_norm": "7b57867185d03d82",
        "head/0": "549b77f3d9a0133f",
        "head/1": "64c1ec30cf6c0377",
        "unit/b0/attn/wk/0": "4569e7eda9c4d9c2",
        "unit/b0/attn/wk/1": "511eaec24e5663f5",
        "unit/b0/attn/wo/0": "8b6ec011b35b6435",
        "unit/b0/attn/wo/1": "e8113f8542942a25",
        "unit/b0/attn/wq/0": "d0ff23540da40f4b",
        "unit/b0/attn/wq/1": "6a539cf5d612e3e6",
        "unit/b0/attn/wv/0": "09d3d521735cc028",
        "unit/b0/attn/wv/1": "b9d5229b2c17a97c",
        "unit/b0/ln1": "45ffe65e6503877a",
        "unit/b0/ln2": "496527c61ef0b613",
        "unit/b0/mlp/w_down/0": "1ac69d3ffce48c55",
        "unit/b0/mlp/w_down/1": "a9d5cd9b743f8472",
        "unit/b0/mlp/w_gate/0": "1477535700622319",
        "unit/b0/mlp/w_gate/1": "cda44bd63a365ddf",
        "unit/b0/mlp/w_up/0": "5ab1d6a7b34726d9",
        "unit/b0/mlp/w_up/1": "1624fd338277b02f",
    },
}
REFERENCE = {
    "tiny": PROGRAM["tiny"],     # bf16: the same tree on both sides
    "tiny-w4": {                 # dequantized to bf16
        "embed": "cb873fad9a4351ea",
        "final_norm": "7b57867185d03d82",
        "head": "e95ef8454ba43fcb",
        "unit/b0/attn/wk": "600c6df00c45cbb5",
        "unit/b0/attn/wo": "66a8c2cff0857c51",
        "unit/b0/attn/wq": "87fb6d9e05e8ff49",
        "unit/b0/attn/wv": "2c7400ac98cdcf33",
        "unit/b0/ln1": "45ffe65e6503877a",
        "unit/b0/ln2": "496527c61ef0b613",
        "unit/b0/mlp/w_down": "447352331387adbc",
        "unit/b0/mlp/w_gate": "e50f0ac03f30d625",
        "unit/b0/mlp/w_up": "1615f23aad134dda",
    },
}
# tiny's reference logits of a fixed sequence of 48 tokens, rows 20-35
LOGITS = {"f32": "8aa0dcd79be2a347", "fp8": "910c29851844a026"}


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


@pytest.mark.parametrize("name", ["tiny", "tiny-w4"])
def test_program_weights_as_before(name):
    cj = _config(name)
    assert _digest(dense.program_weights(cj, weights.seed_key(SEED))) \
        == PROGRAM[name]


@pytest.mark.parametrize("name", ["tiny", "tiny-w4"])
def test_reference_weights_as_before(name):
    cj = _config(name)
    assert _digest(dense.reference_weights(cj, weights.seed_key(SEED))) \
        == REFERENCE[name]


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_logits_rows_as_before(mode):
    cj = _config("tiny")
    w = dense.reference_weights(cj, weights.seed_key(SEED))
    tokens = jnp.asarray((np.arange(48) * 37 + 11) % cj["vocab_size"],
                         jnp.int32)
    f = jax.jit(lambda w, t, s: dense.logits_rows(cj, w, t, s, 16, mode))
    assert _digest({"x": f(w, tokens, np.int32(20))})["x"] == LOGITS[mode]


def test_the_dense_config_loads_dense():
    assert arch.load(_config("tiny")).__file__ == dense.__file__


@pytest.mark.parametrize("name", ["mla", "../dense", 3])
def test_unknown_arch_raises_and_lists_the_modules(name):
    with pytest.raises(ValueError, match=r"unknown arch.*'dense'"):
        arch.load(dict(_config("tiny"), arch=name))


@pytest.mark.parametrize("keys", [
    {"num_experts": 64},                               # experts
    {"num_local_experts": 8},
    {"n_routed_experts": 64, "moe_intermediate_size": 1408},
    {"kv_lora_rank": 512},                             # latent KV
    {"layer_types": ["sliding_attention", "full_attention"]},
])
def test_dense_refuses_what_it_does_not_build(keys):
    with pytest.raises(ValueError, match="dense decoder builds"):
        dense.model_config(dict(_config("tiny"), **keys))


def test_dense_takes_one_layer_type():
    cj = dict(_config("tiny"), layer_types=["full_attention"] * 2)
    assert dense.model_config(cj).n_layers == 2


def test_an_architecture_is_added_by_files_alone(tmp_path):
    spec = checkout.make(tmp_path)
    assert not (BENCH / "arch" / "stub.py").exists()
    r = harness.run(tmp_path, spec, "tiny-stub.open", 2**31 + 3, 2.0,
                    False, time.perf_counter(), require_chip=False)
    assert r["correct"], r["checks"]
    calls = (tmp_path / "bench" / "arch" / "stub.calls").read_text().split()
    assert {"model_config", "layout", "program_weights",
            "reference_weights", "logits_rows"} <= set(calls)
