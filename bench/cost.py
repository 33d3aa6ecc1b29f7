"""Work that one decode step needs, from the model's shapes alone.

What is counted is what the algorithm needs, whatever implements it:

* every weight read once, at its stored precision (bf16: 2 bytes a
  parameter; w4: half a byte a parameter plus one f32 scale per output
  channel of each stored matrix), except the embedding table, of which
  a step needs only the rows of its tokens -- unless the head is tied
  to it, when the head reads the whole table;
* the keys and values of the live positions of the active lanes, read
  once, and the new position's written once (bf16 cache);
* the logits written once (bf16);
* FLOPs: 2 x matmul parameters x lanes, plus 4 x heads x head size per
  live key per layer (scores and the weighted sum of values).

A program that reads fewer bytes than this (packed weights consumed as
they are, a cache that reads only live positions) comes closer to the
least time; none can read fewer and still compute the step.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    weight_bits: int          # 16 (bf16) or 4 (w4 bit-planes)

    @classmethod
    def from_config(cls, cj: dict) -> "Shape":
        d, h = cj["hidden_size"], cj["num_attention_heads"]
        return cls(layers=cj["num_hidden_layers"], d=d, heads=h,
                   kv_heads=cj["num_key_value_heads"],
                   head_dim=cj.get("head_dim") or d // h,
                   d_ff=cj["intermediate_size"], vocab=cj["vocab_size"],
                   tied=bool(cj["tie_word_embeddings"]),
                   qkv_bias=bool(cj["attention_bias"]),
                   weight_bits=16 if cj["serve"]["weights"] == "bf16" else 4)


def _layer_matrices(s: Shape):
    """(in size, out channels) of each stored matrix of one layer, with
    the output channels as the storage scales them: the w4 store keeps
    one scale per entry of a leaf's last axis."""
    return [(s.d * s.heads, s.head_dim),              # wq (d, H, hd)
            (s.d * s.kv_heads, s.head_dim),           # wk (d, KV, hd)
            (s.d * s.kv_heads, s.head_dim),           # wv
            (s.heads * s.head_dim, s.d),              # wo (H, hd, d)
            (s.d, s.d_ff),                            # w_gate
            (s.d, s.d_ff),                            # w_up
            (s.d_ff, s.d)]                            # w_down


def matmul_params(s: Shape) -> int:
    """Parameters multiplied once per token: the layers' matrices and
    the head."""
    per_layer = sum(k * n for k, n in _layer_matrices(s))
    return s.layers * per_layer + s.d * s.vocab


def _matrix_bytes(s: Shape, k: int, n: int) -> float:
    if s.weight_bits == 16:
        return 2.0 * k * n
    return k * n * s.weight_bits / 8 + 4.0 * n


def weight_bytes(s: Shape, lanes: int) -> float:
    """Bytes of weights one decode step of ``lanes`` tokens must read."""
    per_layer = sum(_matrix_bytes(s, k, n) for k, n in _layer_matrices(s))
    per_layer += 4.0 * 2 * s.d                        # two f32 norm weights
    if s.qkv_bias:
        per_layer += 2.0 * (s.heads + 2 * s.kv_heads) * s.head_dim
    total = s.layers * per_layer + 4.0 * s.d          # final norm
    total += _matrix_bytes(s, s.d, s.vocab)           # head: whole table
    if not s.tied:                                    # the tokens' rows
        total += _matrix_bytes(s, lanes, s.d)
    return total


def kv_bytes_per_position(s: Shape) -> int:
    """Keys and values of one position over all layers, bf16."""
    return s.layers * 2 * s.kv_heads * s.head_dim * 2


def decode_step(s: Shape, lanes: int, live_keys: int):
    """(FLOPs, bytes) of one decode step of ``lanes`` active lanes that
    attend over ``live_keys`` positions in all (summed over lanes, each
    lane's count including its new position)."""
    flops = 2.0 * matmul_params(s) * lanes
    flops += 4.0 * s.layers * s.heads * s.head_dim * live_keys
    nbytes = weight_bytes(s, lanes)
    nbytes += kv_bytes_per_position(s) * (live_keys + lanes)  # read + write
    nbytes += 2.0 * s.vocab * lanes                           # logits
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the larger of compute and memory time at the
    chip's peaks, and which of the two it is."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
