"""Production training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --data 4 --model 2 --steps 100 --batch 8 --seq 256

On a real cluster the same entry point runs under ``jax.distributed``
(one process per host); the mesh axes and sharding rules are identical.
``--smoke`` uses the reduced config.  Fault tolerance: restarts from the
latest checkpoint in --ckpt-dir automatically.
"""

from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.launch.mesh import make_mesh
from repro.launch.sharding import batch_sharding, opt_sharding, \
    params_sharding
from repro.models.model import LM
from repro.train import checkpoint as ckpt_mod
from repro.train import data as data_mod
from repro.train import optimizer as opt_mod
from repro.train.runner import RunnerConfig, Trainer
from repro.train.step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pod", type=int, default=1)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-path", default=None)
    args = ap.parse_args()

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    model = LM(cfg)
    mesh = make_mesh(args.data, args.model, args.pod)
    opt_cfg = opt_mod.OptConfig(lr=args.lr, warmup_steps=10,
                                total_steps=args.steps)
    dcfg = data_mod.DataConfig(
        global_batch=args.batch, seq_len=args.seq, vocab=cfg.vocab,
        path=args.data_path,
        src_len=args.seq if cfg.is_encdec else None,
        d_model=cfg.d_model if cfg.is_encdec else None)
    pipe = data_mod.Pipeline(dcfg, host_id=jax.process_index(),
                             n_hosts=jax.process_count())

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        p_shard = params_sharding(params, mesh)
        params = jax.device_put(params, p_shard)
        opt_state = opt_mod.init(params, opt_cfg)
        opt_state = jax.device_put(
            opt_state, opt_sharding(opt_state, p_shard, mesh))

        # params/opt_state are committed to their shardings by device_put;
        # batches get an explicit sharding so host arrays scatter correctly.
        step = make_train_step(model, opt_cfg, accum=args.accum)
        sample = pipe.batch(0)

        def jitted(p, o, b):
            b = jax.device_put(b, batch_sharding(b, mesh))
            return _inner(p, o, b)

        _inner = jax.jit(step, donate_argnums=(0, 1))

        start = 0
        latest = ckpt_mod.latest_step(args.ckpt_dir)
        trainer = Trainer(
            RunnerConfig(total_steps=args.steps,
                         ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir),
            jitted, params, opt_state, pipe)
        if latest is not None:
            start = trainer._restore()
            print(f"resuming from step {start}")
        end, metrics = trainer.run(start)
        print(f"finished at step {end}: {metrics}")


if __name__ == "__main__":
    main()
