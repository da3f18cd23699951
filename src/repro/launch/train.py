"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
        [--reduced] [--steps 100] [--stage1 20] [--optimizer adamw|lomo|galore] \
        [--ckpt-dir DIR] [--compress]

On CPU (JAX_PLATFORMS=cpu) use --reduced (smoke-scale); a TPU runs the
published widths.  Checkpoints are written, and resumed from, only when
--ckpt-dir is given (atomic and resumable, see repro.train.driver).
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--stage1", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "lomo", "galore"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint every steps/5 into (and resume from) "
                         "DIR; default: no checkpoints")
    ap.add_argument("--compress", action="store_true",
                    help="int8 gradient compression before reduction")
    ap.add_argument("--fused-optimizer", action="store_true",
                    help="fuse the optimizer step into the reversible "
                         "backward walk (repro.train.fused, DESIGN.md §13): "
                         "per-layer updates as cotangents are produced, no "
                         "full gradient tree; adamw/lomo only, requires a "
                         "reversible config")
    ap.add_argument("--hbm-budget-gb", type=float, default=None,
                    help="fit per-layer activation policies into this budget "
                         "(repro.memory planner); default: config/80 GiB")
    ap.add_argument("--plan", action="store_true",
                    help="run the memory planner even without an explicit "
                         "--hbm-budget-gb")
    ap.add_argument("--moe-backend", default=None,
                    choices=["einsum", "grouped"],
                    help="override ModelConfig.moe_backend (grouped = "
                         "sort-based dropless dispatch, repro.kernels.moe)")
    ap.add_argument("--ep", type=int, default=0,
                    help="expert-parallel degree (kernels/moe/ep): shards "
                         "experts+tokens over a dedicated 'expert' mesh "
                         "axis with an all-to-all dispatch; the CPU backend "
                         "is given that many host devices so --reduced "
                         "smoke runs exercise the real multi-device path")
    ap.add_argument("--layer-groups", type=int, default=0,
                    help="lean parameterization (DESIGN.md §14): share each "
                         "main-stack layer's matrices across N layer groups "
                         "(must divide the depth; requires a reversible "
                         "config) — params and optimizer state shrink by "
                         "the sharing factor")
    ap.add_argument("--delta-rank", type=int, default=0,
                    help="per-layer low-rank A·B delta on every shared "
                         "matrix (B zero-init: exact no-op at step 0); "
                         "0 = pure sharing; needs --layer-groups")
    ap.add_argument("--use-flash-kernel", action="store_true",
                    help="flash attention on the train path (Pallas fwd+bwd "
                         "kernels on TPU, tiled pure-JAX fallback here; "
                         "O(S) attention residuals, DESIGN.md §8)")
    ap.add_argument("--audit-every", type=int, default=0, metavar="N",
                    help="reversible audit mode (needs --telemetry): every N "
                         "steps re-walk the stack layer by layer outside the "
                         "train jit, emitting per-layer reconstruction error, "
                         "per-policy backward time/residual-byte attribution "
                         "(layer_audit events) and MoE routing telemetry "
                         "(moe_route events); gate with `trace validate "
                         "--max-reconstruction-err` (DESIGN.md §12)")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write a schema-versioned telemetry JSONL to PATH: "
                         "per-step loss/grad-norm/step-time, per-window "
                         "throughput + MFU + estimator-drift memory gauges, "
                         "compile and checkpoint durations (repro.obs; "
                         "inspect with `python -m repro.launch.trace "
                         "summarize PATH`)")
    args = ap.parse_args()

    import jax
    if args.ep > 1:
        # sizes the CPU backend only (before it initialises); a TPU host
        # keeps its own devices
        jax.config.update("jax_num_cpu_devices", args.ep)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.models.model import Model
    from repro.optim.adamw import AdamW, cosine_schedule
    from repro.optim.galore import GaLore
    from repro.optim.lomo import LoMo
    from repro.train.driver import RunConfig, train

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.layer_groups > 0:
        cfg = cfg.replace(num_layer_groups=args.layer_groups,
                          delta_rank=args.delta_rank)
        if args.reduced:
            # re-clamp to the reduced depth like reduce_config does
            import math
            cfg = cfg.replace(num_layer_groups=math.gcd(
                cfg.num_layers, args.layer_groups))
    if args.moe_backend is not None:
        cfg = cfg.replace(moe_backend=args.moe_backend)
    if args.use_flash_kernel:
        cfg = cfg.replace(use_flash_kernel=True)
    if args.ep > 0:
        from repro.core import settings
        from repro.launch.mesh import make_debug_mesh
        cfg = cfg.replace(expert_parallel=args.ep)
        n_dev = len(jax.devices())
        if n_dev % args.ep != 0:
            raise SystemExit(f"--ep {args.ep} does not divide the "
                             f"{n_dev} available devices")
        settings.set_ep_mesh(make_debug_mesh(data=n_dev // args.ep,
                                             expert=args.ep))
    model = Model(cfg)
    print(f"[train] {cfg.name}: {model.num_params() / 1e6:.1f}M params, "
          f"family={cfg.family}, reversible={cfg.reversible}")

    opt = {"adamw": AdamW(lr=args.lr, weight_decay=0.01,
                          lr_schedule=cosine_schedule(10, args.steps)),
           "lomo": LoMo(lr=args.lr),
           "galore": GaLore(lr=args.lr)}[args.optimizer]
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch,
                    num_hosts=jax.process_count(),
                    host_id=jax.process_index())
    rc = RunConfig(total_steps=args.steps, stage1_steps=args.stage1,
                   ckpt_every=max(args.steps // 5, 1),
                   ckpt_dir=args.ckpt_dir,
                   log_every=args.log_every, n_micro=args.n_micro,
                   audit_every=args.audit_every,
                   fused_optimizer=args.fused_optimizer)
    memory_plan = None
    if args.plan or args.hbm_budget_gb is not None:
        from repro.memory.planner import plan as make_plan
        # per-device microbatch: the pipeline shards the global batch across
        # hosts, then grad accumulation splits each host's share by n_micro
        per_dev = max(args.batch // (jax.process_count() * args.n_micro), 1)
        memory_plan = make_plan(cfg, budget_gb=args.hbm_budget_gb,
                                batch=per_dev,
                                seq=args.seq, optimizer=args.optimizer,
                                fused_optimizer=args.fused_optimizer)
    _, _, losses = train(model, opt, dc, rc, plan=memory_plan,
                         telemetry=args.telemetry)
    if args.telemetry:
        print(f"[train] telemetry -> {args.telemetry} "
              f"(python -m repro.launch.trace summarize {args.telemetry})")
    if losses:
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    else:
        # resume-from-latest found a checkpoint at/past --steps: no new steps
        print(f"[train] done: nothing to do (checkpoint in {args.ckpt_dir} "
              f"already at step >= {args.steps})")


if __name__ == "__main__":
    main()
