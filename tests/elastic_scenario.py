"""Elastic-restart scenario, run under 4 fake devices by
test_train.py::test_elastic_restart_subprocess.

Phase 1: train 6 steps on a (data=2, model=2) mesh, checkpoint.
Phase 2: "lose" half the data-parallel groups -> rebuild on (1, 2),
restore, continue to step 10.  The global batch and RNG counters are
unchanged, so the post-restart loss sequence must equal a reference run
that never failed.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import tempfile

import numpy as np

import jax

from repro import configs
from repro.launch.mesh import make_mesh
from repro.launch.sharding import batch_sharding, params_sharding
from repro.models.model import LM
from repro.train import checkpoint as ckpt
from repro.train import data as data_mod
from repro.train import optimizer as opt_mod
from repro.train.step import make_train_step


def run_steps(mesh, model, params, opt_state, pipe, opt_cfg, lo, hi):
    step_fn = make_train_step(model, opt_cfg)
    losses = []
    with jax.set_mesh(mesh):
        p_shard = params_sharding(params, mesh)
        params = jax.device_put(params, p_shard)
        jitted = jax.jit(step_fn)
        for s in range(lo, hi):
            batch = pipe.batch(s)
            batch = jax.device_put(batch, batch_sharding(batch, mesh))
            params, opt_state, m = jitted(params, opt_state, batch)
            losses.append(float(m["loss"]))
    return params, opt_state, losses


def main():
    cfg = configs.get_config("qwen2-0.5b", smoke=True)
    model = LM(cfg)
    opt_cfg = opt_mod.OptConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    pipe = data_mod.Pipeline(data_mod.DataConfig(
        global_batch=4, seq_len=16, vocab=cfg.vocab))

    params0 = model.init(jax.random.PRNGKey(0))
    opt0 = opt_mod.init(params0, opt_cfg)

    # reference: 10 uninterrupted steps on the big mesh
    _, _, ref_losses = run_steps(make_mesh(2, 2), model, params0, opt0,
                                 pipe, opt_cfg, 0, 10)

    # phase 1: 6 steps on (2, 2), checkpoint
    tmp = tempfile.mkdtemp(prefix="elastic_")
    params, opt_state, l1 = run_steps(make_mesh(2, 2), model, params0,
                                      opt0, pipe, opt_cfg, 0, 6)
    ckpt.save(tmp, 6, {"params": params, "opt": opt_state})

    # phase 2: node loss -> (1, 2) mesh, restore, continue
    like = {"params": params0, "opt": opt0}
    tree, meta = ckpt.restore(tmp, like)
    assert meta["step"] == 6
    _, _, l2 = run_steps(make_mesh(1, 2), model, tree["params"],
                         tree["opt"], pipe, opt_cfg, 6, 10)

    got = l1 + l2
    err = max(abs(a - b) for a, b in zip(got, ref_losses))
    assert err < 2e-2, (got, ref_losses)
    print(f"ELASTIC_OK max_loss_delta={err:.5f}")


if __name__ == "__main__":
    main()
