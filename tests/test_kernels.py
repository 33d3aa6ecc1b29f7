"""Pallas kernels vs pure-jnp oracles (interpret mode; shape/dtype sweeps)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand_int(rng, bits, shape):
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1))
    return jnp.asarray(rng.integers(lo, hi, shape), jnp.int8)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("axis", [0, 1])
def test_pack_unpack_roundtrip(bits, axis):
    rng = np.random.default_rng(0)
    x = _rand_int(rng, bits, (64, 32))
    planes = ref.pack_bitplanes(x, bits, axis=axis)
    back = ref.unpack_bitplanes(planes, axis=axis, signed=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x, np.int32))


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("mnk", [(16, 128, 64), (32, 256, 128),
                                 (128, 128, 512), (64, 256, 1024)])
def test_quant_matmul_vs_oracle(bits, mnk):
    m, n, k = mnk
    rng = np.random.default_rng(1)
    a = _rand_int(rng, 8, (m, k))
    w = _rand_int(rng, bits, (k, n))
    scale = jnp.asarray(rng.uniform(0.001, 0.1, n), jnp.float32)
    wp = ref.pack_bitplanes(w, bits, axis=0)
    got = ops.quant_matmul(a, wp, scale, bits=bits, interpret=True,
                           block_m=32, block_n=128, block_k=256)
    want = ref.quant_matmul(a, wp, scale, bits=bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)
    # and against plain integer matmul (exactness of the decomposition)
    exact = (np.asarray(a, np.int64) @ np.asarray(w, np.int64)
             ).astype(np.float32) * np.asarray(scale)[None, :]
    np.testing.assert_allclose(np.asarray(got), exact, rtol=1e-6)


@pytest.mark.parametrize("ba,bw", [(4, 4), (8, 4), (4, 8)])
def test_popcount_matmul_vs_oracle(ba, bw):
    m, n, k = 16, 64, 128
    rng = np.random.default_rng(2)
    a = _rand_int(rng, ba, (m, k))
    w = _rand_int(rng, bw, (k, n))
    ap = ref.pack_bitplanes(a, ba, axis=1)
    wp = ref.pack_bitplanes(w, bw, axis=0)
    got = ops.popcount_matmul(ap, wp, interpret=True,
                              block_m=8, block_n=32, block_k=64)
    want = np.asarray(a, np.int64) @ np.asarray(w, np.int64)
    np.testing.assert_array_equal(np.asarray(got), want)
    oracle = ref.popcount_matmul(ap, wp, a_signed=True, w_signed=True)
    np.testing.assert_array_equal(np.asarray(oracle), want)


def test_popcount_matmul_tiles_k():
    """K of 8192 runs as two 128-word blocks of the accumulation grid."""
    m, n, k = 8, 128, 8192
    rng = np.random.default_rng(5)
    a = _rand_int(rng, 4, (m, k))
    w = _rand_int(rng, 4, (k, n))
    got = ops.popcount_matmul(ref.pack_bitplanes(a, 4, axis=1),
                              ref.pack_bitplanes(w, 4, axis=0),
                              interpret=True, block_k=4096)
    want = np.asarray(a, np.int64) @ np.asarray(w, np.int64)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dim,target,align,want", [
    (896, 512, 256, 896),      # qwen2 d_model: no 256-multiple divides
    (4864, 512, 256, 256),     # qwen2 d_ff
    (4864, 128, 128, 128),
    (8, 128, 32, 8),           # smaller than one tile: the full dim
    (152, 128, 128, 152),      # 4864 bits of packed words
])
def test_block_tiles_are_mosaic_aligned(dim, target, align, want):
    from repro.kernels.bitserial_matmul import _tile
    assert _tile(dim, target, align) == want


def test_popcount_matches_engine_semantics():
    """Cross-layer: Pallas popcount path == Compute RAM engine idot.

    Both implement sum_t a_t*b_t by bit-level AND/add -- verify they
    agree end-to-end (unsigned int4, one output column per CR column).
    """
    from repro.core import harness, programs
    from repro.core import ref as cref
    rng = np.random.default_rng(3)
    prog, lay = programs.idot(4, rows=128)
    cols = 8
    a = rng.integers(0, 16, (lay.tuples, cols), dtype=np.uint64)
    b = rng.integers(0, 16, (lay.tuples, cols), dtype=np.uint64)
    got_engine = harness.unpack_acc(
        harness.run_program(prog, lay, {"a": a, "b": b}, cols), lay)

    # same dot products via the packed kernel: per column c,
    # acc[c] = a[:, c] . b[:, c]
    K = ((lay.tuples + 31) // 32) * 32
    a_pad = np.zeros((cols, K), np.int8)
    b_pad = np.zeros((K, cols), np.int8)
    a_pad[:, :lay.tuples] = a.T
    b_pad[:lay.tuples, :] = b
    ap = ref.pack_bitplanes(jnp.asarray(a_pad), 4, axis=1)
    wp = ref.pack_bitplanes(jnp.asarray(b_pad), 4, axis=0)
    out = ops.popcount_matmul(ap, wp, a_signed=False, w_signed=False,
                              interpret=True, block_m=8, block_n=8,
                              block_k=32)
    np.testing.assert_array_equal(np.diag(np.asarray(out)), got_engine)


def test_quantize_roundtrip_error():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (128, 64)), jnp.float32)
    q, s = ops.quantize(x, bits=8, axis=1)
    err = np.abs(np.asarray(q, np.float32) * np.asarray(s)[None, :] -
                 np.asarray(x))
    assert err.max() < np.abs(np.asarray(x)).max() / 100


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 128, 32), (4, 256, 64)])
def test_flash_attention_vs_oracle(causal, shape):
    from repro.kernels.flash_attention import attention_ref, flash_attention
    bh, s, hd = shape
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(0, 1, (bh, s, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (bh, s, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (bh, s, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_matches_model_chunked_path():
    """Pallas kernel == the model zoo's chunked-jnp attention."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import chunked_attention
    b, s, h, hd = 2, 128, 4, 32
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(0, 1, (b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, s, h, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    want = chunked_attention(q, k, v, pos, pos, causal=True, chunk=64)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s, hd)
    got = flash_attention(qf, kf, vf, causal=True, block_q=64, block_k=64,
                          interpret=True)
    got = jnp.moveaxis(got.reshape(b, h, s, hd), 1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Bit-plane backends (repro.kernels.bitplane_ops): the ripple add and
# the lane-axis popcount fold behind the packed compiled executor
# ---------------------------------------------------------------------------
def test_planes_add_none_elision_oracle():
    """planes_add with None (known-zero) planes == dense add/sub.

    Exhausts every None/dense pattern over 4-bit operands with and
    without a carry-in; subtraction is the asymmetric case (a-0 vs 0-b
    elide differently), so both orders are covered by construction.
    """
    from itertools import product

    from repro.kernels import bitplane_ops as bp

    rng = np.random.default_rng(0)
    w = 4
    av = rng.integers(0, 2, (w, 8)).astype(np.uint32)
    bv = rng.integers(0, 2, (w, 8)).astype(np.uint32)
    cv = rng.integers(0, 2, (8,)).astype(np.uint32)
    for mask_a, mask_b, cin, sub in product(
            range(1 << w), range(1 << w), (False, True), (False, True)):
        a = [jnp.asarray(av[i]) if mask_a >> i & 1 else None
             for i in range(w)]
        b = [jnp.asarray(bv[i]) if mask_b >> i & 1 else None
             for i in range(w)]
        ad = [jnp.zeros(8, jnp.uint32) if p is None else p for p in a]
        bd = [jnp.zeros(8, jnp.uint32) if p is None else p for p in b]
        ci = jnp.asarray(cv) if cin else None
        cd = jnp.asarray(cv) if cin else jnp.zeros(8, jnp.uint32)
        got, gc = bp.planes_add(a, b, ci, sub=sub)
        want, wc = bp.planes_add(ad, bd, cd, sub=sub)
        for g, x in zip(got, want):
            gd = jnp.zeros(8, jnp.uint32) if g is None else g
            np.testing.assert_array_equal(np.asarray(gd & 1),
                                          np.asarray(x & 1))
        gcd = jnp.zeros(8, jnp.uint32) if gc is None else gc
        np.testing.assert_array_equal(np.asarray(gcd & 1),
                                      np.asarray(wc & 1))


def test_planes_add_matches_integer_arithmetic():
    """Dense planes_add == uint add/sub mod 2^w with exact carry-out."""
    from repro.kernels import bitplane_ops as bp

    rng = np.random.default_rng(1)
    w, n = 6, 64
    a = rng.integers(0, 1 << w, n)
    b = rng.integers(0, 1 << w, n)
    c = rng.integers(0, 2, n)
    for sub in (False, True):
        ap = [jnp.asarray((a >> i & 1).astype(np.uint32)) for i in range(w)]
        bpl = [jnp.asarray((b >> i & 1).astype(np.uint32)) for i in range(w)]
        out, cout = bp.planes_add(ap, bpl, jnp.asarray(c.astype(np.uint32)),
                                  sub=sub)
        got = sum(np.asarray(p & 1).astype(np.int64) << i
                  for i, p in enumerate(out))
        full = a - b - c if sub else a + b + c
        np.testing.assert_array_equal(got, full % (1 << w))
        np.testing.assert_array_equal(np.asarray(cout & 1).astype(bool),
                                      (full < 0) if sub
                                      else (full >> w).astype(bool))


@pytest.mark.parametrize("lanes,words,width", [(3, 4, 5), (8, 16, 8),
                                               (17, 33, 12)])
def test_lane_fold_pallas_matches_jnp(lanes, words, width):
    """The Pallas positional-popcount fold (interpret mode) == the jnp
    carry-save tree == per-bit integer summation, on ragged lane/word
    counts that exercise the grid padding."""
    from repro.kernels import bitplane_ops as bp

    rng = np.random.default_rng(2)
    m = min(width, 4)
    x = jnp.asarray(rng.integers(0, 1 << 32, (m, lanes, words),
                                 dtype=np.uint64).astype(np.uint32))
    got = bp.lane_fold_pallas(x, width, block_w=16, interpret=True)
    want = bp.lane_fold_jnp([x[i] for i in range(m)], width)
    for i in range(width):
        w = np.zeros(words, np.uint32) if want[i] is None \
            else np.asarray(want[i])
        np.testing.assert_array_equal(np.asarray(got[i]), w)
    # integer oracle: the column at (word wi, bit) holds, per lane, the
    # integer sum_i(plane_i_bit << i); the fold sums lanes mod 2^width
    xs = np.asarray(x, np.uint64)
    folded = np.asarray(got, np.uint64)
    for wi in range(0, words, max(1, words // 5)):
        for bit in (0, 31):
            tot = sum(sum((int(xs[i][t, wi]) >> bit & 1) << i
                          for i in range(m))
                      for t in range(lanes))
            have = sum((int(folded[i, wi]) >> bit & 1) << i
                       for i in range(width))
            assert have == tot % (1 << width), (wi, bit)


def test_use_pallas_fold_selection_rule(monkeypatch):
    """Auto mode: Pallas only for packed folds on a TPU backend above
    the column threshold; env var force-overrides either way."""
    from repro.kernels import bitplane_ops as bp

    monkeypatch.delenv(bp._ENV, raising=False)
    big = bp.PALLAS_FOLD_MIN_COLS // 32
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not bp.use_pallas_fold(8, big, True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert bp.use_pallas_fold(8, big, True)
    assert not bp.use_pallas_fold(1, 1, True)      # below threshold
    assert not bp.use_pallas_fold(8, big, False)   # never unpacked
    monkeypatch.setenv(bp._ENV, "jnp")
    assert not bp.use_pallas_fold(8, big, True)
    monkeypatch.setenv(bp._ENV, "pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert bp.use_pallas_fold(1, 1, True)


def test_lane_fold_dispatch_env_override(monkeypatch):
    """lane_fold under REPRO_BITPLANE_BACKEND=pallas (interpret) is
    bit-identical to the jnp tree on packed planes."""
    from repro.kernels import bitplane_ops as bp

    rng = np.random.default_rng(3)
    width, lanes, words = 6, 5, 7
    planes = [None if i == 2 else
              jnp.asarray(rng.integers(0, 1 << 32, (lanes, words),
                                       dtype=np.uint64).astype(np.uint32))
              for i in range(width)]
    want = bp.lane_fold_jnp(planes, width)
    monkeypatch.setenv(bp._ENV, "pallas")
    got = bp.lane_fold(planes, width, packed=True, interpret=True)
    for g, w in zip(got, want):
        gd = np.zeros(words, np.uint32) if g is None else np.asarray(g)
        wd = np.zeros(words, np.uint32) if w is None else np.asarray(w)
        np.testing.assert_array_equal(gd, wd)
