"""Compile the main path for a TPU v5e that is described, not attached.

Nothing runs: each test lowers a kernel or a jitted step at real widths
and hands it to the TPU compiler installed with JAX, which refuses what
the chip would refuse (unaligned blocks, operand types Mosaic cannot
feed the MXU, programs that do not fit the chip's memory).  Results and
times come only from a chip run (``chip_smoke.py``).

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU library, and every worker collects the
same tests.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import bitplane_ops, bitserial_matmul
from repro.models import qweight
from repro.models.model import LM
from repro.serve.engine import merge_slot

V5E_HBM_BYTES = 16 * 2**30
QWEN2_KN = [(896, 4864), (4864, 896), (896, 128)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache altogether
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_lane_fold_compiles_at_engine_shape(one_chip):
    """idot4 at 64 blocks of 40 columns folds 57 lanes of 80 words with
    a 15-bit accumulator -- the fold the engine hands to Pallas."""
    m, lanes, words = 15, 57, 80
    assert lanes * words * 32 >= bitplane_ops.PALLAS_FOLD_MIN_COLS
    x = jax.ShapeDtypeStruct((m, lanes, words), jnp.uint32,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda x: bitplane_ops.lane_fold_pallas(x, m)).lower(x).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("k,n", QWEN2_KN)
def test_quant_matmul_compiles(one_chip, k, n, bits):
    args = _on(one_chip, (jax.ShapeDtypeStruct((8, k), jnp.int8),
                          jax.ShapeDtypeStruct((bits, k // 32, n), jnp.uint32),
                          jax.ShapeDtypeStruct((n,), jnp.float32)))
    compiled = jax.jit(lambda a, w, s: bitserial_matmul.quant_matmul(
        a, w, s, bits=bits)).lower(*args).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("k,n", QWEN2_KN)
def test_popcount_matmul_compiles(one_chip, k, n):
    args = _on(one_chip, (jax.ShapeDtypeStruct((8, 8, k // 32), jnp.uint32),
                          jax.ShapeDtypeStruct((4, k // 32, n), jnp.uint32)))
    compiled = jax.jit(bitserial_matmul.popcount_matmul).lower(
        *args).compile()
    assert _has_kernel(compiled)


@pytest.fixture(scope="module")
def qwen2(one_chip):
    model = LM(configs.get_config("qwen2-0.5b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, _on(one_chip, params)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, used


def test_qwen2_decode_step_compiles(one_chip, qwen2):
    """The serve engine's decode step: 8 slots, 1024-token caches."""
    model, params = qwen2
    caches = _on(one_chip, jax.eval_shape(lambda: model.init_cache(8, 1024)))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    _fits(jax.jit(model.decode_step).lower(
        params, caches, tokens, pos).compile())


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@pytest.mark.parametrize("name,slots", [("qwen2-0.5b", 64),
                                        ("h2o-danube-1.8b", 32)])
def test_decode_step_never_repeats_the_ring(one_chip, name, slots):
    """The decode step at the serving benchmark's backlog shape (slots x
    1024 positions) computes attention per KV group: no f32 array holds
    the ring repeated to every query head (slots * 1024 * H * hd
    elements), and the scratch memory stays under what one layer's
    cache and weights hold."""
    cfg = configs.get_config(name)
    model = LM(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: model.init_cache(slots, 1024))
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step).lower(
        _on(one_chip, params), _on(one_chip, caches), tokens, pos).compile()

    repeated = slots * 1024 * cfg.n_heads * cfg.hd
    for dims in re.findall(r"f32\[([0-9,]+)\]", compiled.as_text()):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        assert n != repeated, f"f32[{dims}]"
    one_layer = (_nbytes(caches["unit"]) + _nbytes(params["unit"])) \
        // cfg.n_layers
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer


def test_qwen2_prefill_compiles(one_chip, qwen2):
    model, params = qwen2
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32, sharding=one_chip)
    _fits(jax.jit(lambda p, t: model.prefill(p, tokens=t, capacity=1024))
          .lower(params, tokens).compile())


def test_merge_slot_compiles_at_chat_shape(one_chip, qwen2):
    """The serve engine's cache merge: one admission into 64 slots of
    3072-token caches, one program whatever the slot."""
    model, _ = qwen2
    caches = _on(one_chip, jax.eval_shape(lambda: model.init_cache(64, 3072)))
    one = _on(one_chip, jax.eval_shape(lambda: model.init_cache(1, 3072)))
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _fits(merge_slot.lower(caches, one, i).compile())


def test_w4_decode_step_ops_carry_the_scope_names(one_chip, qwen2):
    """The compiled program's operations name the ``attention`` and
    ``dq`` scopes in their metadata: a trace's operation names join
    them."""
    model, params = qwen2
    params = jax.eval_shape(
        lambda p: qweight.quantize_tree(p, bits=4), params)
    caches = _on(one_chip, jax.eval_shape(lambda: model.init_cache(8, 1024)))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    txt = jax.jit(model.decode_step).lower(
        _on(one_chip, params), caches, tokens, pos).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', txt)
    assert any("/attention/" in n and "dot_general" in n for n in names)
    assert any("/dq/" in n for n in names)


def test_deepseek_w4_decode_step_fits_with_its_scopes(one_chip):
    """DeepSeek-V2-Lite at published widths, every matrix at 4 bits: the
    engine's counted decode step at the backlog shape (32 slots x 1024
    positions of latent cache) fits the chip with its output caches,
    its expert products lower to the grouped-matmul kernel, and the
    ``mla`` and ``moe`` scopes reach the operations' metadata."""
    model = LM(configs.get_config("deepseek-v2-lite"))
    params = jax.eval_shape(lambda k: qweight.quantize_tree(
        model.init(k), bits=4), jax.random.PRNGKey(0))
    caches = _on(one_chip, jax.eval_shape(lambda: model.init_cache(32, 1024)))
    tokens = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, c, t, s: model.decode_step(p, c, t, s, counts=True)
    ).lower(_on(one_chip, params), caches, tokens, pos).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < 14 * 2**30
    assert _has_kernel(compiled)
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    assert any("/mla/" in n for n in names)
    assert any("/moe/" in n for n in names)
