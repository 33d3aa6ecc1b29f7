"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM sizing, and unsupported collectives all
surface here.  Results (memory analysis, cost analysis, collective
schedule, roofline terms) are written as JSON for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all --out results/
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
"""

import argparse
import json
import os
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import analysis, shapes as shp
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_sharding, cache_sharding,
                                   opt_sharding, params_sharding)
from repro.models.model import LM
from repro.train import optimizer as opt_mod
from repro.train.step import make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P


def _aval(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def apply_overrides(cfg, overrides: dict):
    """dataclasses.replace with dotted keys ("moe.dispatch_chunks")."""
    import dataclasses
    flat, nested = {}, {}
    for key, v in (overrides or {}).items():
        if "." in key:
            head, tail = key.split(".", 1)
            nested.setdefault(head, {})[tail] = v
        else:
            flat[key] = v
    for head, sub in nested.items():
        flat[head] = dataclasses.replace(getattr(cfg, head), **sub)
    return dataclasses.replace(cfg, **flat)


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               opt_overrides: dict | None = None,
               mesh_shape: tuple | None = None) -> dict:
    opt_overrides = dict(opt_overrides or {})
    wq_bits = opt_overrides.pop("wq_bits", None)
    cfg = configs.get_config(arch)
    if opt_overrides:
        cfg = apply_overrides(cfg, opt_overrides)
    if not shp.applicable(cfg, shape_name):
        return {"arch": arch, "shape": shape_name,
                "multi_pod": multi_pod, "status": "skipped",
                "reason": shp.skip_reason(cfg, shape_name)}

    if mesh_shape is not None:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(*mesh_shape)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    model = LM(cfg)
    spec = shp.input_specs(cfg, shape_name)
    kind = shp.SHAPES[shape_name]["kind"]
    t0 = time.time()

    with jax.set_mesh(mesh):
        if wq_bits:
            from repro.models.qweight import quantize_tree
            params_avals = jax.eval_shape(
                lambda k: quantize_tree(model.init(k), bits=wq_bits),
                jax.random.PRNGKey(0))
        else:
            params_avals = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_shard = params_sharding(params_avals, mesh)
        rep = NamedSharding(mesh, P())

        if kind == "train":
            opt_cfg = opt_mod.OptConfig()
            opt_avals = jax.eval_shape(
                lambda p: opt_mod.init(p, opt_cfg), params_avals)
            o_shard = opt_sharding(opt_avals, p_shard, mesh)
            b_shard = batch_sharding(spec["batch"], mesh)
            step = make_train_step(model, opt_cfg)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_avals, opt_avals, spec["batch"])

        elif kind == "prefill":
            def prefill_step(params, tokens, enc_out=None, enc_pos=None):
                return model.prefill(params, tokens=tokens,
                                     enc_out=enc_out, enc_pos=enc_pos)
            args = [params_avals, spec["tokens"]]
            in_sh = [p_shard, batch_sharding(spec["tokens"], mesh)]
            if cfg.is_encdec:
                args += [spec["enc_out"], spec["enc_pos"]]
                in_sh += [batch_sharding(spec["enc_out"], mesh),
                          batch_sharding(spec["enc_pos"], mesh)]
            jitted = jax.jit(prefill_step, in_shardings=tuple(in_sh))
            lowered = jitted.lower(*args)

        else:  # decode
            seq = shp.SHAPES[shape_name]["seq"]
            b = shp.SHAPES[shape_name]["batch"]
            cache_avals = jax.eval_shape(
                lambda: model.init_cache(b, seq))
            c_shard = cache_sharding(cache_avals, mesh)

            def serve_step(params, caches, tokens, pos,
                           enc_out=None, enc_pos=None):
                return model.decode_step(params, caches, tokens, pos,
                                         enc_out=enc_out, enc_pos=enc_pos)
            args = [params_avals, cache_avals, spec["tokens"], spec["pos"]]
            in_sh = [p_shard, c_shard,
                     batch_sharding(spec["tokens"], mesh),
                     batch_sharding(spec["pos"], mesh)]
            if cfg.is_encdec:
                args += [spec["enc_out"], spec["enc_pos"]]
                in_sh += [batch_sharding(spec["enc_out"], mesh),
                          batch_sharding(spec["enc_pos"], mesh)]
            jitted = jax.jit(serve_step, in_shardings=tuple(in_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(*args)

        compiled = lowered.compile()

    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost = dict(cost) if cost else {}
    text = compiled.as_text()
    coll = analysis.collective_bytes(text)
    scan_mult = analysis.scan_trip_multiplier(text)
    chips = mesh.devices.size

    mem_d = {}
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_d[k] = int(v)

    res = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "chips": int(chips),
        "compile_s": round(t_compile, 1),
        "params_b": int(cfg.param_count()),
        "active_params_b": int(cfg.active_param_count()),
        "hlo_flops": float(cost.get("flops", -1)),
        "hlo_bytes": float(cost.get("bytes accessed", -1)),
        "scan_trip_multiplier": float(scan_mult),
        "collective_bytes": coll.total_bytes,
        "collective_by_kind": coll.bytes_by_kind,
        "collective_ops": coll.count,
        "memory_analysis": mem_d,
    }
    res.update(analysis.analytic_terms(cfg, shape_name, chips))
    if wq_bits:
        # params move at 1 B/elt (w8) or 0.5 B/elt (w4 planes) vs bf16
        n_total = cfg.param_count()
        res["analytic_bytes"] -= 2.0 * n_total \
            - (n_total if wq_bits == 8 else n_total / 2)
        res["wq_bits"] = wq_bits
    return res


ALL_CELLS = [(a, s) for a in configs.list_archs() for s in shp.SHAPES]


def use_host_pods():
    """Stand the production pods in with 512 CPU devices.  Entry points
    call this before their first device query; importing this module
    leaves ``XLA_FLAGS`` alone."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"


def main():
    use_host_pods()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--degraded", action="store_true",
                    help="elastic re-mesh after node loss: (data=8, model=16)"
                         " = half a pod; proves the re-lowered topology"
                         " compiles")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    cells = ALL_CELLS if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    mesh_shape = (8, 16) if args.degraded else None
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__" + (
                "degraded" if args.degraded else
                ("multi" if mp else "single"))
            fp = out / f"{tag}.json"
            if fp.exists():
                print(f"[skip] {tag} (exists)")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                res = lower_cell(arch, shape, mp, mesh_shape=mesh_shape)
            except Exception as e:                    # noqa: BLE001
                res = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-3000:]}
            fp.write_text(json.dumps(res, indent=1))
            print(f"[done] {tag}: {res['status']}", flush=True)


if __name__ == "__main__":
    main()
