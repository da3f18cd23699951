"""Names of the program's ``jax.named_scope`` scopes and Pallas kernels.

Every device op of the training step carries its scope path in its HLO
``op_name`` metadata (the profiler's ``tf_op`` stat), e.g.
``jit(fused_train_step)/walk.update/.../transpose(jvp(attn))/dot_general``.
A trace reader attributes an op to a scope when the scope is a whole
component of that path, bare or inside ``jvp(...)`` / ``transpose(...)``.
This module is the one list of those names: the code opens them from here,
and the tests check that each appears in the compiled step.

Phases of the fused train step (``repro.train.fused``; the unfused stack
rules in ``repro.core.reversible`` reuse the walk names):

  prelude       embed and the shared-prefix vjp
  walk.forward  the gradient-free forward walk over the main stacks
  head_loss     final norm, LM head, chunked cross-entropy and its vjp
  walk.probe    the clip-norm backward walk
  walk.update   the update backward walk (the accumulate walk and the
                per-layer update loop under gradient accumulation)
  opt.update    the optimizer's per-layer, per-group and final updates

Inside every backward walk, per layer:

  rev.invert     ``block_inv``, every fixed-point pass included
  rev.recompute  the forward that ``jax.vjp`` linearises
  rev.vjp        applying that vjp to the layer's output cotangents

Sublayers (``repro.models``): ``attn`` (adapters, projections, rope and the
kernel or einsum path), ``moe.router``, ``moe.dispatch`` (sort or one-hot
dispatch and the padding), ``moe.experts``, ``moe.combine``, ``moe.shared``,
``mlp`` (adapters and the SwiGLU), ``norm``.
"""
from __future__ import annotations

PRELUDE = "prelude"
WALK_FORWARD = "walk.forward"
HEAD_LOSS = "head_loss"
WALK_PROBE = "walk.probe"
WALK_UPDATE = "walk.update"
OPT_UPDATE = "opt.update"
PHASES = (PRELUDE, WALK_FORWARD, HEAD_LOSS, WALK_PROBE, WALK_UPDATE,
          OPT_UPDATE)

REV_INVERT = "rev.invert"
REV_RECOMPUTE = "rev.recompute"
REV_VJP = "rev.vjp"
REVERSIBLE = (REV_INVERT, REV_RECOMPUTE, REV_VJP)

ATTN = "attn"
MOE_ROUTER = "moe.router"
MOE_DISPATCH = "moe.dispatch"
MOE_EXPERTS = "moe.experts"
MOE_COMBINE = "moe.combine"
MOE_SHARED = "moe.shared"
MLP = "mlp"
NORM = "norm"
SUBLAYERS = (ATTN, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE,
             MOE_SHARED, MLP, NORM)

SCOPES = PHASES + REVERSIBLE + SUBLAYERS

#: ``name=`` of every ``pallas_call``: the kernel's HLO instruction is
#: ``%<name>.<n>`` and its op_name ends in ``<name>/pallas_call``
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv", "gmm", "paged_decode",
           "rmsnorm", "mamba_ssd", "rwkv6_scan")
