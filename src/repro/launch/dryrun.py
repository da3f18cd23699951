import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on the
production meshes without allocating a single full-size weight.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-moe-a2.7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]

``plan`` mode runs the memory planner (repro.memory) instead of XLA lowering:

  PYTHONPATH=src python -m repro.launch.dryrun plan --config qwen2_moe_a2_7b
  PYTHONPATH=src python -m repro.launch.dryrun plan --all [--budget-gb 80] \
      [--optimizer lomo] [--batch 8] [--seq 4096]

printing the per-layer activation-policy table and the estimated device peak
against the HBM budget for one or all configs.

Everything is ShapeDtypeStructs: parameters via Model.abstract_params(),
decode caches via jax.eval_shape(Model.init_cache).  ``compile()`` succeeding
proves the sharding config is coherent (no mismatched collectives, fits
per-device HBM); memory_analysis/cost_analysis feed EXPERIMENTS.md §Roofline.
"""
import argparse
import json
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCHS, SHAPES, ShapeConfig, get_config, shapes_for
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.optim.adamw import AdamW
from repro.train.trainer import make_train_step

# collective accounting shared with benchmarks (which cannot import this
# module: the XLA flag above is an import-time side effect)
from repro.distributed.hlo_stats import collective_bytes  # noqa: E402


def abstract_batch(cfg, shape: ShapeConfig, mesh):
    B, S = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    batch = {"tokens": tok}
    if cfg.family == "encdec":
        batch["enc_feats"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq_len, cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        batch["img"] = jax.ShapeDtypeStruct(
            (B, cfg.num_image_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    return batch


def batch_pspecs(batch, mesh):
    return jax.tree_util.tree_map(
        lambda s: shd.batch_pspec(mesh, s.shape[0], len(s.shape),
                                  dim1=s.shape[1] if len(s.shape) > 1 else None),
        batch)


def n_micro_for(cfg, shape: ShapeConfig, mesh, micro_tokens: int = 8192) -> int:
    """Grad-accum microbatches: keep per-device microbatch tokens ~<= target."""
    fsdp = 1
    for a in shd.data_axes(mesh):
        fsdp *= mesh.shape[a]
    tokens_per_dev = shape.global_batch * shape.seq_len / fsdp
    n = max(1, int(tokens_per_dev // micro_tokens))
    while shape.global_batch % (n * fsdp) != 0 and n > 1:
        n -= 1
    return n


def lower_cell(arch: str, shape_name: str, mesh, *, compile_: bool = True,
               model_overrides: Optional[dict] = None,
               micro_tokens: int = 8192,
               seq_parallel: bool = False,
               hsdp: bool = False):
    from repro.core import settings
    cfg = get_config(arch)
    if model_overrides:
        cfg = cfg.replace(**model_overrides)
    shape = SHAPES[shape_name]
    if shape.kind == "prefill" and (not model_overrides
                                    or "attn_q_chunk" not in model_overrides):
        # 32k-token prefill: small q blocks keep f32 score temps bounded
        cfg = cfg.replace(attn_q_chunk=256)
    model = Model(cfg)
    shd.HSDP = hsdp
    if cfg.expert_parallel > 0:
        settings.set_ep_mesh(mesh)
    fa = shd.data_axes(mesh)
    faxis = fa if len(fa) > 1 else fa[0]
    model.batch_spec = P(faxis)
    settings.set_act_spec(P(faxis, "model") if seq_parallel else None)

    aparams = model.abstract_params()
    pspecs = shd.param_pspecs(model.logical_axes(), aparams, mesh)
    result = {"arch": arch, "shape": shape_name,
              "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
              "kind": shape.kind}

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = AdamW(lr=1e-5)
            aopt = jax.eval_shape(opt.init, aparams)
            opt_pspecs = {"m": pspecs, "v": pspecs, "step": P()}
            batch = abstract_batch(cfg, shape, mesh)
            bspecs = batch_pspecs(batch, mesh)
            nm = n_micro_for(cfg, shape, mesh, micro_tokens)
            result["n_micro"] = nm
            step = make_train_step(model, opt, n_micro=nm)
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, opt_pspecs, bspecs),
                out_shardings=(pspecs, opt_pspecs, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(aparams, aopt, batch)
        else:
            B, S = shape.global_batch, shape.seq_len
            extras = {}
            if cfg.family == "encdec":
                extras["enc_feats"] = jax.ShapeDtypeStruct(
                    (B, cfg.encoder_seq_len, cfg.d_model), jnp.dtype(cfg.dtype))
            if cfg.family == "vlm":
                extras["img"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_image_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
            if extras:
                acache = jax.eval_shape(
                    lambda p, ex: model.init_cache(p, B, S, extras=ex),
                    aparams, extras)
            else:
                acache = jax.eval_shape(
                    lambda p: model.init_cache(p, B, S), aparams)
            cspecs = shd.cache_pspecs(acache, mesh, B,
                                      kv_heads=cfg.num_kv_heads)
            if shape.kind == "prefill":
                tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
            else:
                tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            tspec = shd.batch_pspec(mesh, B, 2, dim1=tok.shape[1])

            def serve_step(params, cache, token):
                return model.decode_step(params, cache, token)

            jitted = jax.jit(
                serve_step,
                in_shardings=(pspecs, cspecs, tspec),
                out_shardings=(None, cspecs),
                donate_argnums=(1,))
            lowered = jitted.lower(aparams, acache, tok)

        result["lower_s"] = round(time.time() - t0, 1)
        if not compile_:
            return result, lowered, None
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                result[attr] = int(v)
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):        # older JAX: one dict per program
        cost = cost[0] if cost else None
    if cost:
        result["flops"] = float(cost.get("flops", 0.0))
        result["hlo_bytes"] = float(cost.get("bytes accessed", 0.0))
    result["collectives"] = collective_bytes(compiled.as_text())
    return result, lowered, compiled


def _resolve_arch(name: str) -> str:
    """Accept both the arch id ("qwen2-moe-a2.7b") and its config module
    spelling ("qwen2_moe_a2_7b")."""
    from repro.configs.base import _MODULE_FOR
    if name in ARCHS:
        return name
    for arch, module in _MODULE_FOR.items():
        if name == module:
            return arch
    raise SystemExit(f"unknown config {name!r}; known: {', '.join(ARCHS)}")


def plan_main(argv):
    """`dryrun plan`: print planner budget tables — no XLA lowering at all."""
    from repro.memory.planner import plan
    ap = argparse.ArgumentParser(prog="dryrun plan")
    ap.add_argument("--config", "--arch", dest="arch", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--budget-gb", type=float, default=None,
                    help="override ModelConfig.hbm_budget_gb / the 80G default")
    ap.add_argument("--batch", type=int, default=8,
                    help="per-device microbatch")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--optimizer", default="lomo",
                    choices=["adamw", "lomo", "galore"],
                    help="lomo (default) is the paper's single-device "
                         "scenario: fused update, no optimizer state; "
                         "adamw shows the full m/v-state floor instead")
    ap.add_argument("--fused-optimizer", action="store_true",
                    help="plan against the fused optimizer-in-backward step "
                         "(repro.train.fused, DESIGN.md §13): grads floor = "
                         "non-stack remainder + one layer slice; adamw/lomo")
    ap.add_argument("--reduced", action="store_true",
                    help="plan the smoke-scale configs (CPU tests)")
    ap.add_argument("--layer-groups", type=int, default=0,
                    help="lean parameterization (DESIGN.md §14): share each "
                         "main-stack layer's big matrices across N layer "
                         "groups — params AND optimizer state shrink by the "
                         "sharing factor; the report adds the factor line")
    ap.add_argument("--delta-rank", type=int, default=0,
                    help="per-layer low-rank delta rank on the shared "
                         "matrices (0 = pure sharing); needs --layer-groups")
    ap.add_argument("--moe-backend", default=None,
                    choices=["einsum", "grouped"],
                    help="override ModelConfig.moe_backend for the plan "
                         "trace (grouped shrinks MoE dispatch residuals)")
    ap.add_argument("--ep", type=int, default=0,
                    help="plan MoE configs under expert parallelism: the "
                         "trace runs the shard_map a2a dispatch path and "
                         "the report surfaces the per-layer a2a comm bytes")
    args = ap.parse_args(argv)

    if args.ep > 0:
        from repro.core import settings
        from repro.launch.mesh import make_debug_mesh
        n_dev = len(jax.devices())
        settings.set_ep_mesh(make_debug_mesh(data=n_dev // args.ep,
                                             expert=args.ep))

    archs = ARCHS if args.all else [_resolve_arch(args.arch or "qwen2-moe-a2.7b")]
    unfit = []
    for arch in archs:
        cfg = get_config(arch, reduced=args.reduced)
        if args.moe_backend is not None:
            cfg = cfg.replace(moe_backend=args.moe_backend)
        if args.ep > 0 and cfg.num_experts > 0:
            cfg = cfg.replace(expert_parallel=args.ep)
        if args.layer_groups > 0 and cfg.reversible \
                and cfg.family != "hybrid":
            # hybrid (zamba2) already shares its attn block as a built-in
            # layer group; dense/moe archs opt in here
            import math
            cfg = cfg.replace(
                num_layer_groups=math.gcd(cfg.num_layers,
                                          args.layer_groups),
                delta_rank=args.delta_rank)
        try:
            p = plan(cfg, budget_gb=args.budget_gb, batch=args.batch,
                     seq=args.seq, optimizer=args.optimizer,
                     fused_optimizer=args.fused_optimizer)
        except Exception as e:  # noqa: BLE001 — report and continue
            print(f"[FAIL] {arch}: {type(e).__name__}: {str(e)[:300]}",
                  flush=True)
            unfit.append(arch)
            continue
        print(p.report(), flush=True)
        print()
        if not p.fits:
            unfit.append(arch)
    print(f"{len(archs) - len(unfit)}/{len(archs)} configs fit their budget")
    return 1 if unfit else 0


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "plan":
        return plan_main(sys.argv[2:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--hsdp", action="store_true")
    ap.add_argument("--micro-tokens", type=int, default=8192)
    ap.add_argument("--moe-backend", default=None,
                    choices=["einsum", "grouped"])
    ap.add_argument("--ep", type=int, default=0,
                    help="expert-parallel degree: carve an 'expert' axis "
                         "out of the production mesh's data axis and route "
                         "MoE layers through the shard_map a2a dispatch "
                         "(kernels/moe/ep, DESIGN.md §10)")
    args = ap.parse_args()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(expert=max(args.ep, 1)),
                  make_production_mesh(multi_pod=True,
                                       expert=max(args.ep, 1))]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod,
                                       expert=max(args.ep, 1))]

    cells = []
    if args.all:
        for arch in ARCHS:
            for sh in shapes_for(arch):
                cells.append((arch, sh.name))
    else:
        cells.append((args.arch, args.shape))

    results = []
    for mesh in meshes:
        for arch, sh in cells:
            tag = f"{arch} x {sh} @ {tuple(mesh.shape.values())}"
            try:
                overrides = {}
                if args.moe_backend:
                    overrides["moe_backend"] = args.moe_backend
                if args.ep > 0 and get_config(arch).num_experts > 0:
                    # EP only applies to MoE archs — a dense cell under
                    # --all --ep just runs without it
                    overrides["expert_parallel"] = args.ep
                overrides = overrides or None
                res, _, compiled = lower_cell(
                    arch, sh, mesh, micro_tokens=args.micro_tokens,
                    model_overrides=overrides,
                    seq_parallel=args.seq_parallel, hsdp=args.hsdp)
                print(f"[OK]   {tag}  flops={res.get('flops', 0):.3e} "
                      f"coll={sum(res.get('collectives', {}).values()):.3e}B "
                      f"lower={res['lower_s']}s compile={res.get('compile_s')}s",
                      flush=True)
                results.append(res)
            except Exception as e:  # noqa: BLE001 — report and continue
                print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:400]}", flush=True)
                results.append({"arch": arch, "shape": sh, "error": str(e)[:2000],
                                "mesh": "x".join(str(s) for s in mesh.shape.values())})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if "error" in r]
    print(f"\n{len(results) - len(bad)}/{len(results)} cells OK")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
