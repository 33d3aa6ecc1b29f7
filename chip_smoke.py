#!/usr/bin/env python3
"""Bring-up check: the repo's two layers on a TPU, through their normal
entry points, with every result checked.

  python chip_smoke.py [--seed N]       one chip: serve + simulator phases
  python chip_smoke.py --four-chips     four chips: sharded training only

* serve -- qwen2-0.5b at its published widths in ``LM`` under
  ``ServeEngine``, random bf16 weights from the seed, 16 seeded
  requests.  Every generated token is checked against a teacher-forced
  full-sequence ``LM.apply``.
* simulator -- the paper's add, mul and dot programs at int4, int8 and
  bf16 through ``engine.execute_blocks`` at 1 block and at 64 blocks,
  bit-exact against ``core/ref.py``; then one int8 ``fabric_matmul``.
* four chips -- ``make_train_step`` on a 2x2 (data, model) mesh against
  a one-device mesh in the same process.

Each phase prints one line with its result and seconds, and any failure
raises.  The last line of stdout is a JSON object naming the device.
There is no CPU fallback: without a TPU the script exits non-zero.
"""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
#: a generated token passes when its logit under the teacher-forced
#: reference is within this much of the reference's top logit.  The
#: decode step and the full-sequence forward round bf16 in different
#: orders, so near-ties may flip; at these random weights the logits
#: have a spread of about 0.08 and a top value of about 0.35.
LOGIT_MARGIN = 0.02
#: |loss(2x2 mesh) - loss(one device)|: bf16 matmuls reduced in a
#: different order across the model axis.
LOSS_TOL = 0.02
#: the simulator's second block count: 64 blocks of 40 columns is where
#: the dot programs' lane fold covers >= PALLAS_FOLD_MIN_COLS columns
SIM_BLOCKS = 64


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _secs(t0: float) -> float:
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Serve phase
# ---------------------------------------------------------------------------
def _reference_gaps(model, params, reqs, width: int):
    """Teacher-forced check of every generated token.

    Runs ``LM.apply`` over ``prompt + out[:-1]`` (tail-padded to
    ``width``: the forward is causal) and returns, per generated token,
    the reference's top logit minus the token's logit, and whether the
    token is the reference's argmax.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def score(params, tokens, targets):
        logits = model.apply(params, tokens=tokens)[0].astype(jnp.float32)
        picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return (jnp.max(logits, -1) - picked,
                jnp.argmax(logits, -1) == targets)

    gaps, exact = [], []
    for lo in range(0, len(reqs), 8):
        group = reqs[lo:lo + 8]
        tokens = np.zeros((8, width), np.int32)
        targets = np.zeros((8, width), np.int32)
        for b, r in enumerate(group):
            seq = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
            tokens[b, :len(seq) - 1] = seq[:-1]
            targets[b, :len(seq) - 1] = seq[1:]
        g, e = jax.device_get(score(params, tokens, targets))
        for b, r in enumerate(group):
            p = len(r.prompt)
            gaps.append(g[b, p - 1:p - 1 + len(r.out)])
            exact.append(e[b, p - 1:p - 1 + len(r.out)])
    return np.concatenate(gaps), np.concatenate(exact)


def serve_phase(cfg, seed: int, *, n_requests: int = 16, max_new: int = 32,
                prompt_range=(32, 512), batch_slots: int = 8,
                capacity: int = 1024) -> None:
    import jax

    from repro.models.model import LM
    from repro.serve.engine import Request, ServeEngine

    t0 = time.perf_counter()
    model = LM(cfg)
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    t_init = _secs(t0)

    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_range[0], prompt_range[1] + 1, n_requests)
    reqs = [Request(rid=i, max_new=max_new,
                    prompt=rng.integers(0, cfg.vocab, n).astype(np.int32))
            for i, n in enumerate(lens)]
    t1 = time.perf_counter()
    eng = ServeEngine(model, params, batch_slots=batch_slots,
                      capacity=capacity)
    for r in reqs:
        eng.add(r)
    eng.run()
    t_run = _secs(t1)

    not_done = [r.rid for r in reqs
                if r.status != "done" or len(r.out) != max_new]
    check(not not_done and not eng.rejected,
          f"serve: requests not done {not_done}, "
          f"rejected {[r.rid for r in eng.rejected]}")
    eng.kv.assert_empty()
    buckets = sorted({min(1 << (int(n) - 1).bit_length(), capacity)
                      for n in lens})
    check(eng.stats["prefill_compiles"] == len(buckets),
          f"serve: {eng.stats['prefill_compiles']} prefill compiles for "
          f"buckets {buckets}")

    t2 = time.perf_counter()
    gaps, exact = _reference_gaps(model, params, reqs,
                                  prompt_range[1] + max_new)
    t_ref = _secs(t2)
    print(f"serve: reference max_logit_gap={gaps.max():.6f} "
          f"margin={LOGIT_MARGIN} argmax_exact={int(exact.sum())}/"
          f"{exact.size}", flush=True)
    check(gaps.max() <= LOGIT_MARGIN,
          f"serve: a token is {gaps.max():.6f} below the reference's top "
          f"logit (margin {LOGIT_MARGIN})")
    st = eng.stats
    print(f"serve: ok arch={cfg.name} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab} requests={len(reqs)} "
          f"done={len(reqs)} rejected=0 tokens={exact.size} "
          f"prompt_lens={int(lens.min())}..{int(lens.max())} "
          f"prefill_compiles={st['prefill_compiles']} buckets={buckets} "
          f"kv_empty=True steps={st['steps']} "
          f"prefill_s={st['prefill_s']:.3f} "
          f"decode_s={st['decode_s']:.3f} "
          f"decode_n={st['decode_n']} init_s={t_init:.3f} "
          f"run_s={t_run:.3f} reference_s={t_ref:.3f} "
          f"seconds={_secs(t0):.3f}", flush=True)


# ---------------------------------------------------------------------------
# Simulator phase
# ---------------------------------------------------------------------------
def _bf16_bits(rng, shape, elo: int, ehi: int):
    s = rng.integers(0, 2, shape).astype(np.uint64)
    e = rng.integers(elo, ehi, shape).astype(np.uint64)
    m = rng.integers(0, 128, shape).astype(np.uint64)
    bits = (s << np.uint64(15)) | (e << np.uint64(7)) | m
    return np.where(rng.random(shape) < 0.1, 0, bits).astype(np.uint64)


def _sim_cases():
    """(name, (program, layout), operand maker, readback, oracle)."""
    from repro.core import floatprog, harness, programs, ref

    def ints(n):
        return lambda rng, shape: rng.integers(0, 1 << n, shape,
                                               dtype=np.uint64)

    def field(lay, arr):
        return harness.unpack_field(arr, lay, "d")

    def acc(lay, arr):
        return harness.unpack_acc(arr, lay)

    def fdot(lay, arr):
        return floatprog.fdot_result(arr, floatprog.BF16)

    bf_mid = lambda rng, shape: _bf16_bits(rng, shape, 100, 150)
    bf_dot = lambda rng, shape: _bf16_bits(rng, shape, 85, 170)
    cases = []
    for n in (4, 8):
        cases += [
            (f"iadd{n}", programs.iadd(n), ints(n), field,
             lambda a, b, n=n: ref.iadd(a, b, n)),
            (f"imul{n}", programs.imul(n), ints(n), field,
             lambda a, b, n=n: ref.imul(a, b, n)),
            (f"idot{n}", programs.idot(n), ints(n), acc, ref.idot),
        ]
    cases += [
        ("bf16_add", programs.bf16_add(), bf_mid, field, ref.bf16_add),
        ("bf16_mul", programs.bf16_mul(), bf_mid, field, ref.bf16_mul),
        ("bf16_dot", programs.bf16_dot(), bf_dot, fdot, ref.bf16_dot),
    ]
    return cases


def _run_blocks(prog, lay, make, read, oracle, rng, blocks: int, cols: int):
    """One execute_blocks launch checked bit-exact; returns (compile_s,
    replay_s, pallas_fold)."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine, harness

    a = make(rng, (blocks, lay.tuples, cols))
    b = make(rng, (blocks, lay.tuples, cols))
    arr = np.stack([harness.pack_state(lay, {"a": a[i], "b": b[i]}, cols)
                    for i in range(blocks)])
    states = engine.CRState(jnp.asarray(arr),
                            jnp.zeros((blocks, cols), bool),
                            jnp.ones((blocks, cols), bool))
    run = lambda: jax.block_until_ready(
        engine.execute_blocks(prog, states, executor="compiled"))
    t0 = time.perf_counter()
    out = run()
    compile_s = _secs(t0)
    replay_s = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        run()
        replay_s = min(replay_s, _secs(t0))
    got = np.asarray(out.array)
    for i in range(blocks):
        want = np.asarray(oracle(a[i], b[i]), np.uint64)
        have = np.asarray(read(lay, got[i]), np.uint64)
        check(np.array_equal(have, want),
              f"simulator: {prog.name} block {i}/{blocks} differs from "
              f"the reference")
    hlo = jax.jit(lambda s: engine.execute_blocks(prog, s)).lower(
        states).as_text()
    return compile_s, replay_s, "tpu_custom_call" in hlo


def simulator_phase(seed: int, *, blocks=(1, SIM_BLOCKS), cols: int = 40,
                    fabric_shape=(8, 896, 128)) -> None:
    from repro.core import engine
    from repro.kernels import bitplane_ops
    from repro.pim import fabric

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    kernel_launches = 0
    cases = _sim_cases()
    for name, (prog, lay), make, read, oracle in cases:
        line = [f"simulator: {name} exact tuples={lay.tuples} "
                f"packed={engine.default_packed(prog)}"]
        for nb in blocks:
            compile_s, replay_s, pallas = _run_blocks(
                prog, lay, make, read, oracle, rng, nb, cols)
            kernel_launches += pallas
            line.append(f"blocks={nb}: pallas_fold={pallas} "
                        f"compile_s={compile_s:.3f} "
                        f"replay_s={replay_s:.6f}")
        print(" | ".join(line), flush=True)
    check(kernel_launches > 0,
          f"simulator: no launch ran the Pallas lane fold (threshold "
          f"{bitplane_ops.PALLAS_FOLD_MIN_COLS} columns)")

    m, k, n = fabric_shape
    t1 = time.perf_counter()
    x = rng.integers(-128, 128, (m, k))
    w = rng.integers(-128, 128, (k, n))
    res = fabric.fabric_matmul(x, w, nbits=8, signed=True)
    check(np.array_equal(np.asarray(res.out, np.int64),
                         x.astype(np.int64) @ w.astype(np.int64)),
          "simulator: fabric_matmul differs from the int64 matmul")
    print(f"simulator: fabric_matmul int8 ({m}x{k})@({k}x{n}) exact "
          f"seconds={_secs(t1):.3f}", flush=True)
    print(f"simulator: ok programs={len(cases)} blocks={list(blocks)} "
          f"pallas_fold_launches={kernel_launches} "
          f"seconds={_secs(t0):.3f}", flush=True)


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------
def _train(model, mesh, seed: int, steps: int, batch: int, seq: int):
    """``steps`` train steps on ``mesh``; returns (losses, bytes of the
    parameters on each device)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import (batch_sharding, opt_sharding,
                                       params_sharding)
    from repro.train import data as data_mod
    from repro.train import optimizer as opt_mod
    from repro.train.step import make_train_step

    opt_cfg = opt_mod.OptConfig(lr=1e-3, warmup_steps=1, total_steps=steps)
    pipe = data_mod.Pipeline(data_mod.DataConfig(
        seed=seed, global_batch=batch, seq_len=seq, vocab=model.cfg.vocab))
    key = jax.random.PRNGKey(seed)
    with jax.set_mesh(mesh):
        p_shard = params_sharding(jax.eval_shape(model.init, key), mesh)
        params = jax.jit(model.init, out_shardings=p_shard)(key)
        o_shard = opt_sharding(
            jax.eval_shape(lambda p: opt_mod.init(p, opt_cfg), params),
            p_shard, mesh)
        opt_state = jax.jit(lambda p: opt_mod.init(p, opt_cfg),
                            out_shardings=o_shard)(params)
        b_shard = batch_sharding(pipe.batch(0), mesh)
        step = jax.jit(make_train_step(model, opt_cfg),
                       in_shardings=(p_shard, o_shard, b_shard),
                       out_shardings=(p_shard, o_shard,
                                      NamedSharding(mesh, P())),
                       donate_argnums=(0, 1))
        losses = []
        for s in range(steps):
            batch_s = jax.device_put(pipe.batch(s), b_shard)
            params, opt_state, metrics = step(params, opt_state, batch_s)
            losses.append(float(metrics["loss"]))
    per_dev = collections.Counter()
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    return losses, dict(sorted(per_dev.items()))


def four_chip_phase(cfg, seed: int, *, steps: int = 3, batch: int = 8,
                    seq: int = 128) -> None:
    import jax

    from repro.launch.mesh import make_mesh
    from repro.models.model import LM

    t0 = time.perf_counter()
    model = LM(cfg)
    ref, ref_bytes = _train(model, make_mesh(1, 1), seed, steps, batch, seq)
    t_one = _secs(t0)
    t1 = time.perf_counter()
    got, per_dev = _train(model, make_mesh(2, 2), seed, steps, batch, seq)
    t_four = _secs(t1)
    total = sum(ref_bytes.values())
    print(f"four_chips: losses 2x2={[round(x, 6) for x in got]} "
          f"one_device={[round(x, 6) for x in ref]} tol={LOSS_TOL}",
          flush=True)
    print(f"four_chips: param bytes per device {per_dev} "
          f"(one device holds {total})", flush=True)
    check(max(abs(a - b) for a, b in zip(got, ref)) <= LOSS_TOL,
          "four_chips: sharded and one-device losses disagree")
    check(len(per_dev) == len(jax.devices())
          and max(per_dev.values()) < total,
          "four_chips: the parameters are not spread over every device")
    print(f"four_chips: ok arch={cfg.name} mesh=(data=2, model=2) "
          f"steps={steps} batch={batch} seq={seq} "
          f"one_device_s={t_one:.3f} mesh_s={t_four:.3f} "
          f"seconds={_secs(t0):.3f}", flush=True)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, the requests and the data")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training phase (4 chips)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} chips; JAX found {len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__}", flush=True)

    from repro import configs
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = configs.get_config(ARCH)
    if args.four_chips:
        four_chip_phase(cfg, args.seed)
    else:
        serve_phase(cfg, args.seed)
        simulator_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
