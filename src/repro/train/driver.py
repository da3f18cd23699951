"""Fault-tolerant training driver: two-stage RevFFN schedule, periodic
atomic checkpoints, resume-from-latest, and elastic re-lowering.

Restart semantics: the data pipeline is deterministic in (seed, host, step),
so a resumed run replays exactly the remaining data shard — no global
reshuffle barrier, which is also the straggler-mitigation story (a restarted
or re-scheduled replica never blocks others on data state).

``elastic_remesh`` handles node loss: rebuild a smaller mesh, recompute
PartitionSpecs, reshard live state with jax.device_put, re-jit the step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint import manager as ckpt
from repro.core import schedule
from repro.data.pipeline import DataConfig, packed_batches
from repro.train.trainer import make_train_step


@dataclasses.dataclass
class RunConfig:
    total_steps: int = 100
    stage1_steps: int = 20          # adapter warm-up (paper §3.3)
    ckpt_every: int = 20
    ckpt_dir: Optional[str] = None  # None: no checkpoints, no resume
    log_every: int = 10
    n_micro: int = 1
    audit_every: int = 0            # reversible audit cadence (0 = off, §12)
    fused_optimizer: bool = False   # optimizer-in-backward step (§13)


def _predicted_peak_bytes(model, optimizer, batch: int, seq: int,
                          save_memory, fused: bool = False) -> Optional[int]:
    """Static peak-HBM prediction for the drift gauge (repro.memory
    estimator, DESIGN.md §11).  Guarded: telemetry must never take the run
    down, so any estimator failure just disables the prediction."""
    try:
        from repro.memory import estimator as est
        opt_name = type(optimizer).__name__.lower()
        if opt_name not in ("adamw", "lomo", "galore"):
            opt_name = "adamw"
        e = est.estimate(model.cfg, batch, seq, optimizer=opt_name,
                         fused=fused)
        if isinstance(save_memory, (list, tuple)):
            policies = list(save_memory)
        elif save_memory and model.cfg.reversible:
            policies = ["reversible"] * e.n_units
        else:
            policies = ["store"] * e.n_units
        return e.device_total(policies)
    except Exception:  # noqa: BLE001
        return None


def _make_auditor(model, tel, save_memory):
    """Build the layer auditor lazily at the first audit window (None when
    the config has no reversible stack to audit)."""
    from repro.obs.audit import LayerAuditor, policies_for
    policies = policies_for(model, save_memory)
    if policies is None:
        return None
    return LayerAuditor(model, tel, policies)


def train(model, optimizer, data_cfg: DataConfig, run: RunConfig,
          params=None, log_fn: Callable = print,
          fail_at_step: Optional[int] = None, plan=None, telemetry=None):
    """Runs (or resumes) a two-stage fine-tune.  ``fail_at_step`` simulates a
    preemption (raises) for the fault-tolerance tests.  ``plan`` is an
    optional ``repro.memory.planner.MemoryPlan`` (or a raw per-layer policy
    list): the step then runs the planned mixed activation policies instead
    of the all-reversible default.  ``telemetry`` is a JSONL path or a
    ``repro.obs.Telemetry``: the driver then emits per-step loss/grad-norm/
    step-time events, per-window throughput + estimator-drift gauges, and
    checkpoint/compile durations (DESIGN.md §11), and marks each step on a
    profiler trace's timeline (``jax.profiler.StepTraceAnnotation``).  With
    ``run.audit_every > 0`` (and live telemetry) every Nth step additionally
    runs the reversible audit (repro.obs.audit): per-layer reconstruction
    error, per-policy backward-time/residual-byte attribution, and MoE
    routing telemetry, bracketed by a recompile watchdog so an audit that
    perturbs the train step's jit caches is flagged (DESIGN.md §12).

    Timing accounting: jit compile time (the first call of each stage step)
    and checkpoint save/restore time are measured and reported as their own
    metrics — the steady-state step-time histogram and the logged steps/s
    contain neither, so the first log window is no longer skewed by compile
    and checkpoint windows are not skewed by save I/O."""
    tel = obs.as_telemetry(telemetry, role="train", config=model.cfg.name,
                           total_steps=run.total_steps,
                           global_batch=data_cfg.global_batch,
                           seq_len=data_cfg.seq_len, n_micro=run.n_micro)
    owns_tel = telemetry is not None and not hasattr(telemetry, "emit")
    save_memory = True
    if plan is not None:
        save_memory = list(getattr(plan, "policies", plan))
        if hasattr(plan, "report"):
            log_fn(plan.report())
    key = jax.random.PRNGKey(0)
    if params is None:
        params = model.init(key)
    opt_state = optimizer.init(params)
    start_step = 0

    # layer-group tie maps (DESIGN.md §14) travel with every checkpoint:
    # base leaves are only meaningful under the exact layer→group map
    layouts = {s.name: s.layout.describe()
               for s in model.stacks if s.layout is not None} or None

    latest = ckpt.latest_step(run.ckpt_dir) if run.ckpt_dir else None
    if latest is not None:
        t_rs = time.perf_counter()
        (params, opt_state), start_step = ckpt.restore(
            run.ckpt_dir, (params, opt_state), layouts=layouts)
        tel.emit("ckpt_restore", step=start_step,
                 dur_s=time.perf_counter() - t_rs)
        log_fn(f"[driver] resumed from step {start_step}")

    step1 = make_train_step(model, optimizer, n_micro=run.n_micro,
                            mask_fn=schedule.stage1_mask,
                            save_memory=save_memory,
                            fused=run.fused_optimizer)
    step2 = make_train_step(model, optimizer, n_micro=run.n_micro,
                            mask_fn=schedule.stage2_mask,
                            save_memory=save_memory,
                            fused=run.fused_optimizer)
    step1 = obs.instrument_jit(jax.jit(step1, donate_argnums=(0, 1)),
                               "train_step_stage1", tel)
    step2 = obs.instrument_jit(jax.jit(step2, donate_argnums=(0, 1)),
                               "train_step_stage2", tel)

    tokens_per_step = data_cfg.global_batch * data_cfg.seq_len
    micro_b = max(data_cfg.global_batch // run.n_micro, 1)
    memw = None
    if tel.enabled:
        memw = obs.MemoryWatchdog(tel, _predicted_peak_bytes(
            model, optimizer, micro_b, data_cfg.seq_len, save_memory,
            fused=run.fused_optimizer))

    auditor = audit_watch = None
    audit_on = run.audit_every > 0 and tel.enabled

    it = packed_batches(data_cfg, start_step=start_step)
    losses = []
    window_s = 0.0          # steady-state step seconds in this log window
    window_steps = 0        # steps contributing to window_s (compiles excl.)

    def emit_window(step):
        sps = window_steps / max(window_s, 1e-9)
        stage = 1 if step < run.stage1_steps else 2
        win = {"step": step + 1, "stage": stage,
               "loss_mean": float(np.mean(losses[-run.log_every:])),
               "steps_per_s": sps, "steady_steps": window_steps,
               "tokens_per_s": sps * tokens_per_step}
        tel.gauge("train.tokens_per_s").set(win["tokens_per_s"])
        if memw is not None:
            win.update(memw.window_fields())
        tel.emit("train_window", **win)
        log_fn(f"[driver] step {step + 1} stage {stage} "
               f"loss {win['loss_mean']:.4f} "
               f"({sps:.2f} steps/s)")

    for step in range(start_step, run.total_steps):
        batch = next(it)
        fn = step1 if step < run.stage1_steps else step2
        mark = (jax.profiler.StepTraceAnnotation("train", step_num=step)
                if tel.enabled else contextlib.nullcontext())
        t_st = time.perf_counter()
        with mark:
            params, opt_state, metrics = fn(params, opt_state, batch)
            loss = float(metrics["loss"])       # host sync: step is done
        dt = time.perf_counter() - t_st
        losses.append(loss)
        compiled = fn.last_call_compiled
        if compiled:
            tel.gauge("train.compile_s").set(dt)
        else:
            window_s += dt
            window_steps += 1
            tel.histogram("train.step_s").observe(dt)
        grads_finite = bool(metrics.get("grads_finite", True))
        if not grads_finite:
            # the optimizer skipped this update (non-finite global norm,
            # repro.optim clip_guard): count it so a diverging run is
            # visible in telemetry instead of silently frozen
            tel.counter("train.nonfinite_grad_steps").inc()
        tel.emit("train_step", step=step + 1,
                 stage=1 if step < run.stage1_steps else 2, loss=loss,
                 grad_norm=float(metrics["grad_norm"]), step_s=dt,
                 compiled=compiled, grads_finite=grads_finite)
        if audit_on and (step + 1) % run.audit_every == 0:
            if auditor is None:
                auditor = _make_auditor(model, tel, save_memory)
                audit_on = auditor is not None
            if auditor is not None:
                if audit_watch is None:
                    audit_watch = obs.RecompileWatchdog(
                        {"train_step_stage1": step1,
                         "train_step_stage2": step2}, tel, scope="train")
                # warm/check bracket the audit call alone: stage 2's later
                # first compile must not read as an audit-induced recompile
                audit_watch.mark_warm()
                ab = {k: v[:micro_b] for k, v in batch.items()}
                with tel.span("audit", observe=False):
                    auditor.run(params, ab, step + 1)
                audit_watch.check()
        if (step + 1) % run.log_every == 0:
            emit_window(step)
            window_s, window_steps = 0.0, 0
        if run.ckpt_dir and (step + 1) % run.ckpt_every == 0:
            t_sv = time.perf_counter()
            ckpt.save(run.ckpt_dir, step + 1, (params, opt_state),
                      extra_meta={"layouts": layouts})
            save_s = time.perf_counter() - t_sv
            tel.counter("train.ckpt_saves").inc()
            tel.histogram("train.ckpt_save_s").observe(save_s)
            tel.emit("ckpt_save", step=step + 1, dur_s=save_s)
        if fail_at_step is not None and step + 1 == fail_at_step:
            raise RuntimeError(f"simulated preemption at step {step + 1}")
    if window_steps and tel.enabled:
        # trailing partial window: short runs (total_steps not a multiple of
        # log_every) still get throughput + memory-drift gauges
        emit_window(run.total_steps - 1)
    if owns_tel:
        tel.close()
    return params, opt_state, losses


def elastic_remesh(params, opt_state, model, old_mesh, new_mesh):
    """Re-layout live training state onto a smaller/larger mesh after
    membership change.  Returns (params, opt_state, new pspecs)."""
    from repro.distributed import sharding as shd
    aparams = model.abstract_params()
    pspecs = shd.param_pspecs(model.logical_axes(), aparams, new_mesh)
    shardings = jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(new_mesh, s), pspecs)
    params = jax.device_put(params, shardings)
    opt_shardings = {"m": shardings, "v": shardings,
                     "step": jax.sharding.NamedSharding(
                         new_mesh, jax.sharding.PartitionSpec())}
    opt_state = jax.device_put(opt_state, opt_shardings)
    return params, opt_state, pspecs
