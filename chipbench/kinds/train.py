"""Training cells: the program's fused fine-tune step, driven as
``repro.train.driver.train`` drives it (the next batch, the jitted step, the
loss sync), timed over a window of steps.

Set-up builds one object, the compiled step with its state, and drives it
from the seed through the job's first ``check_steps`` steps, through the
window's own call and feed; the window then goes on with the same object.
After the window (and after the peak memory is read and the program's state
is freed) the plain reference runs the same first steps from the same seed,
and ``chipbench.compare`` holds the program's readings against it.
"""
from __future__ import annotations

import gc
import importlib
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, compare, traffic
from chipbench import weights as W

F32, BF16 = jnp.float32, jnp.bfloat16


# ------------------------------------------------------------------ program

def make_optimizer(spec: dict):
    if spec["name"] == "lomo":
        from repro.optim.lomo import LoMo
        return LoMo(lr=float(spec["lr"]),
                    clip_norm=float(spec.get("clip_norm", 0.0)))
    if spec["name"] == "adamw":
        from repro.optim.adamw import AdamW
        keys = ("lr", "b1", "b2", "eps", "weight_decay", "clip_norm")
        return AdamW(**{k: float(spec[k]) for k in keys if k in spec})
    raise ValueError(f"unknown optimizer {spec['name']!r}")


def program_config(cfgj: dict):
    """The program's ``ModelConfig`` for a configuration file, with every
    size the file states checked against it."""
    from repro.configs.base import get_config
    prog = cfgj["program"]
    pc = get_config(prog["config"]).replace(
        num_layers=cfgj["num_hidden_layers"], **prog.get("overrides", {}))
    for key, attr in prog["sizes"].items():
        if getattr(pc, attr) != cfgj[key]:
            raise ValueError(f"{cfgj['name']}: the file states {key}="
                             f"{cfgj[key]!r}, the program runs {attr}="
                             f"{getattr(pc, attr)!r}")
    return pc


def build(cell):
    from repro.core import schedule
    from repro.models.model import Model
    from repro.train.trainer import make_train_step
    job = cell.traffic
    model = Model(program_config(cell.config))
    opt = make_optimizer(job["optimizer"])
    mask = {1: schedule.stage1_mask, 2: schedule.stage2_mask}[job["stage"]]
    step = jax.jit(make_train_step(model, opt, mask_fn=mask,
                                   fused=job["fused"]),
                   donate_argnums=(0, 1))
    return model, opt, step


def _path(p) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def program_counts(model, ref) -> dict:
    """Stack counts of every leaf as the program holds it, after checking
    that the program's tree is the reference's layout: each stack as deep
    as the reference's, a router at least as wide as the published experts
    and at least the experts held (more is padding)."""
    flat = {_path(p): a for p, a in
            jax.tree_util.tree_flatten_with_path(model.abstract_params())[0]}
    dtype = jnp.dtype(model.cfg.dtype)
    counts = {}
    for leaf in ref.layout():
        a = flat.pop(leaf.path, None)
        if a is None:
            raise ValueError(f"the program has no leaf {leaf.path}")
        ns = len(leaf.stack)
        if leaf.experts_last:
            cnt, core = (a.shape[0], a.shape[-1]), a.shape[1:-1]
        else:
            cnt, core = a.shape[:ns], a.shape[ns:]
        ok = tuple(core) == tuple(leaf.core) and a.dtype == dtype
        if ns:
            ok &= cnt[0] == ref.depth(leaf)
        if ns > 1:
            ok &= cnt[1] >= ref.count(leaf.stack[1])
        if not ok:
            raise ValueError(f"leaf {leaf.path}: program {a.shape} "
                             f"{a.dtype}, reference core {leaf.core}")
        counts[leaf.path] = tuple(int(c) for c in cnt)
    if flat:
        raise ValueError(f"program leaves the reference lacks: {list(flat)}")
    return counts


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _names(ref, leaf, n):
    if not leaf.stack:
        return [leaf.path[0]]
    stack, lname = leaf.path[1], ref.lname(leaf)
    return [ref.reading(stack, i, lname) for i in range(n)]


_READ_FNS = {}


def _delta_fn(leaf, counts):
    """Per-layer ‖cur - w0‖ of one leaf, w0 drawn again from the seed."""
    key = (leaf, counts)
    if key not in _READ_FNS:
        def f(cur, k):
            pk = W.path_key(k, leaf.path)
            if not leaf.stack:
                w0 = W.draw(pk, leaf.core, leaf.kind, BF16).astype(F32)
                return jnp.sqrt(jnp.sum(jnp.square(cur.astype(F32) - w0)))[None]

            def one(i):
                w0 = W.draw_stacked(pk, leaf, counts[1:], BF16,
                                    index=(i,)).astype(F32)
                return jnp.sqrt(jnp.sum(jnp.square(cur[i].astype(F32) - w0)))
            return jax.lax.map(one, jnp.arange(counts[0], dtype=jnp.uint32))
        _READ_FNS[key] = jax.jit(f)
    return _READ_FNS[key]


_norm_fn = jax.jit(lambda x: jnp.sqrt(jnp.sum(
    jnp.square(x.astype(F32)), axis=tuple(range(1, x.ndim)))))


def read_deltas(ref, tree, counts, seed: int, scale: float = 1.0) -> dict:
    out, key = {}, W.seed_key(seed)
    for leaf in ref.layout():
        c = counts[leaf.path]
        v = np.asarray(_delta_fn(leaf, c)(_get(tree, leaf.path), key))
        for name, x in zip(_names(ref, leaf, c[0] if c else 1), v):
            out[name] = float(x) * scale
    return out


def read_norms(ref, tree, counts, scale: float = 1.0) -> dict:
    out = {}
    for leaf in ref.layout():
        c = counts[leaf.path]
        x = _get(tree, leaf.path)
        v = (np.asarray(_norm_fn(x)) if leaf.stack
             else [float(jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))))])
        for name, n in zip(_names(ref, leaf, c[0] if c else 1), v):
            out[name] = float(n) * scale
    return out


def read_first_grad(ref, job, params, opt_state, counts, seed):
    """The first step's gradient as the optimizer got it, from its state:
    LoMo's master moved by lr * g, Adam's first moment is (1 - b1) * g."""
    spec = job["optimizer"]
    if spec["name"] == "lomo":
        return read_deltas(ref, opt_state["master"], counts, seed,
                           1.0 / float(spec["lr"]))
    b1 = float(spec.get("b1", 0.9))
    return read_norms(ref, opt_state["m"], counts, 1.0 / (1.0 - b1))


def read_change(ref, job, params, opt_state, counts, seed):
    tree = (opt_state["master"] if job["optimizer"]["name"] == "lomo"
            else params)
    return read_deltas(ref, tree, counts, seed)


# ------------------------------------------------------------------ run

def reference_module(cfgj):
    return importlib.import_module(f"chipbench.reference.{cfgj['reference']}")


def run(cell, seed: int, seconds: float, trace: bool, ctx) -> dict:
    """One run of a training cell; ``ctx`` carries the process's start time,
    spans, compile counter, device record and trace directory."""
    job, cfgj = cell.traffic, cell.config
    refmod = reference_module(cfgj)
    ref = refmod.RevFFN(cfgj)
    model, opt, step = build(cell)
    counts = program_counts(model, ref)
    spans = ctx.spans

    with spans("setup.weights"):
        params = W.make_tree(seed, ref.layout(), counts,
                             jnp.dtype(model.cfg.dtype))
        opt_state = jax.jit(opt.init)(params)
    feed = traffic.train_batches(job, ref.V, seed)

    n_check = int(job["check_steps"])
    prog = {"losses": []}
    check_s = 0.0
    for k in range(n_check):
        batch = next(feed)
        params, opt_state, m = step(params, opt_state, batch)
        prog["losses"].append(float(m["loss"]))
        if k == 0:
            tc = time.perf_counter()
            prog["first_grad"] = read_first_grad(ref, job, params, opt_state,
                                                 counts, seed)
            check_s += time.perf_counter() - tc
    tc = time.perf_counter()
    prog["change"] = read_change(ref, job, params, opt_state, counts, seed)
    check_s += time.perf_counter() - tc
    setup_s = time.perf_counter() - ctx.t0 - check_s

    B, S = job["batch"], job["seq_len"]
    win = min(seconds, float(job.get("trace_seconds", seconds))) if trace \
        else seconds
    compiles0 = ctx.compiles.n
    if trace:
        jax.profiler.start_trace(str(ctx.trace_dir))
    steps, bad = 0, 0
    t_w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with spans("data"):
                batch = next(feed)
            with spans("step_dispatch"):
                params, opt_state, m = step(params, opt_state, batch)
            with spans("loss_sync"):
                loss = float(m["loss"])
            steps += 1
            bad += not math.isfinite(loss)
            if time.perf_counter() - t_w0 >= win:
                break
    t_w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    compiles = ctx.compiles.n - compiles0
    peak = bench.memory_peak_bytes(cell.chips)

    del params, opt_state, m, step
    gc.collect()
    t_ref = time.perf_counter()
    batches = [b for _, b in zip(range(n_check),
                                 traffic.train_batches(job, ref.V, seed))]
    ref_out = refmod.train_steps(ref, job["optimizer"], seed, batches)
    ref_s = time.perf_counter() - t_ref
    numbers = compare.training_numbers(prog, ref_out)
    correct, checks = compare.judge(numbers, cell.limits["checks"])

    window_s = t_w1 - t_w0
    return {
        "correct": correct and bad == 0 and compiles == 0,
        "attempted": steps, "failed": bad,
        "setup_s": setup_s, "window_s": window_s, "peak_bytes": peak,
        "compiles_in_window": compiles, "reference_s": ref_s,
        "numbers": numbers, "checks": checks,
        "e2e": {"train_tokens_per_s": steps * B * S / window_s,
                "setup_s": setup_s},
        "layer_ctx": {"kind": "train", "steps": steps,
                      "tokens_per_step": B * S, "seq_len": S, "batch": B,
                      "window_s": window_s},
    }
