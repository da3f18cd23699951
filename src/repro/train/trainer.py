"""Training step construction: gradient accumulation over microbatches
(scan — lets XLA pipeline the reduce of microbatch k with the backward of
microbatch k+1), optional gradient compression, stage masks, metrics.

``fused=True`` swaps in the fused optimizer-in-backward step
(repro.train.fused, DESIGN.md §13): per-layer updates inside the reversible
backward walk, no full gradient tree.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.obs import scopes
from repro.optim.adamw import global_norm


def validate_ep(cfg):
    """Fail at step-assembly time (instead of inside the MoE layer's
    shard_map on first trace) when expert parallelism is configured without
    an expert mesh axis."""
    if cfg is None or not getattr(cfg, "expert_parallel", 0) > 0:
        return
    from repro.core import settings
    from repro.kernels.moe.ep import EP_AXIS
    mesh = settings.EP_MESH
    if mesh is None or EP_AXIS not in mesh.axis_names:
        raise ValueError(
            f"expert_parallel={cfg.expert_parallel} training needs a "
            f"mesh with an '{EP_AXIS}' axis installed via "
            f"repro.core.settings.set_ep_mesh(mesh) before building the "
            f"train step (launchers do this from --ep); got "
            f"{'no mesh' if mesh is None else mesh.axis_names}")


def accumulator_init(params, compress: Optional[Callable] = None,
                     accum_dtype=None):
    """Gradient-accumulation buffer for ``n_micro > 1``.

    Dtype policy: an explicit ``accum_dtype`` wins; else when ``compress``
    is set the buffer takes the compressor's output dtype per leaf (each
    microbatch's grads are compressed before accumulation, so the buffer
    never has to be wider than what the compressor emits); else f32 — the
    default spends one full-tree f32 buffer to keep the cross-microbatch
    sum exact regardless of the grad/param dtype."""
    if accum_dtype is None and compress is not None:
        out = jax.eval_shape(compress, params)
        return jax.tree_util.tree_map(
            lambda o: jnp.zeros(o.shape, o.dtype), out)
    dt = accum_dtype or jnp.float32
    return jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, dt), params)


def make_train_step(model, optimizer, *, n_micro: int = 1,
                    mask_fn: Optional[Callable] = None,
                    compress: Optional[Callable] = None,
                    save_memory=True, fused: bool = False,
                    accum_dtype=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    batch leaves have leading dim global_batch; grad accumulation splits it
    into ``n_micro`` slices scanned sequentially (activation memory = one
    microbatch).  ``save_memory`` is forwarded to ``model.loss`` — True /
    "half" / False, or a per-layer activation-policy list from the memory
    planner (repro.memory).  ``fused=True`` builds the optimizer-in-backward
    step instead (repro.train.fused): same signature, same updates to f32
    tolerance, no full gradient tree."""
    if fused:
        if compress is not None:
            raise ValueError(
                "fused optimizer does not compose with gradient compression:"
                " per-layer grads are consumed inside the backward walk "
                "before any whole-tree transform could run; drop --compress "
                "or the fused step")
        from repro.train.fused import make_fused_train_step
        return make_fused_train_step(
            model, optimizer, n_micro=n_micro, mask_fn=mask_fn,
            save_memory=save_memory,
            accum_dtype=accum_dtype or jnp.float32)

    validate_ep(getattr(model, "cfg", None))

    def loss_fn(params, mbatch):
        return model.loss(params, mbatch, save_memory=save_memory)

    def train_step(params, opt_state, batch):
        if n_micro > 1:
            gb = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if gb % n_micro != 0:
                # validate before the reshape: otherwise XLA throws a raw
                # shape error naming neither quantity
                raise ValueError(
                    f"global batch {gb} is not divisible by n_micro={n_micro} "
                    f"(remainder {gb % n_micro}); pick n_micro dividing the "
                    f"global batch or pad the batch")
            resh = jax.tree_util.tree_map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
                batch)

            def body(acc, mbatch):
                loss, g = jax.value_and_grad(loss_fn)(params, mbatch)
                if compress is not None:
                    g = compress(g)
                acc_g, acc_l = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, g_: a + g_.astype(a.dtype), acc_g, g)
                return (acc_g, acc_l + loss), None

            zero_g = accumulator_init(params, compress, accum_dtype)
            (grads, loss_sum), _ = jax.lax.scan(body, (zero_g, 0.0), resh)
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            if compress is not None:
                grads = compress(grads)

        mask = mask_fn(params) if mask_fn else None
        gnorm = global_norm(grads)
        with jax.named_scope(scopes.OPT_UPDATE):
            params, opt_state = optimizer.update(grads, opt_state, params,
                                                 mask=mask)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "grads_finite": jnp.isfinite(gnorm),
                   "step": opt_state["step"]}
        return params, opt_state, metrics

    return train_step
