import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Expert-parallel MoE dispatch benchmark (kernels/moe/ep, DESIGN.md §10).

Gives the CPU backend 8 host devices (a TPU host keeps its own) and sweeps
EP ∈ {1, 2, 4} on the reduced MoE config (mesh (1, EP, 8/EP) over
data x expert x model, so tokens shard over exactly the EP axis and the
leftover devices exercise the expert-ffn TP path), writing BENCH_moe_ep.json.  Four gates, all hard-failed:

  * parity: the EP path matches the single-device dense oracle
    (moe_apply_oracle) forward to < 1e-4 at every EP degree;
  * scaling: per-device dispatch payload bytes — MEASURED by replaying the
    production pack plan on the real routing (ep_dispatch_stats), not a
    closed form — scale exactly ∝ 1/EP;
  * traffic: the all-to-all bytes in the compiled forward HLO equal the
    dense-emulation layout the design documents (3 exchanges: rows out,
    expert ids, rows back) — an accidental extra exchange or a capacity
    regression changes the partitioned module and fails here (EP > 1;
    at EP=1 the exchange is degenerate and XLA may elide it);
  * zero recompiles: after the warmup call, repeated invocations at each EP
    degree hit the jit cache (cache size stays 1 — the dispatch plan is
    shape-static, no routing-dependent recompilation).

Also records wall-clock fwd / fwd+grad per EP (CPU dispatch-overhead ratios,
not TPU throughput) and the full-size analytic a2a cost per MoE layer at the
train_4k microbatch (estimator.ep_a2a_cost; nothing allocated).

    PYTHONPATH=src python benchmarks/moe_ep.py [--quick] \
        [--out BENCH_moe_ep.json] [--batch 2] [--seq 256]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import settings
from repro.distributed.hlo_stats import collective_bytes
from repro.kernels.moe.ep import ep_dispatch_stats
from repro.launch.mesh import make_debug_mesh
from repro.memory.estimator import ep_a2a_cost
from repro.models import moe as moe_lib
from repro.models.spec import initialize
from repro.obs import write_bench_json

ARCH = "qwen2-moe-a2.7b"
EP_SWEEP = (1, 2, 4)


def _time(fn, *args, iters=3):
    out = fn(*args)                     # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_ep(cfg0, p, x, want, ep: int, iters: int) -> dict:
    n_dev = len(jax.devices())
    cfg = cfg0.replace(expert_parallel=ep)
    # data=1 so tokens shard over exactly the EP axis (per-device rows show
    # the 1/EP scaling); the leftover devices go to "model" and run the
    # expert-ffn TP path when d_ff_expert divides
    settings.set_ep_mesh(make_debug_mesh(data=1, model=n_dev // ep,
                                         expert=ep))

    fwd = jax.jit(lambda p, x: moe_lib.moe_apply(p, cfg, x)[0])
    grad = jax.jit(jax.grad(lambda p, x: jnp.sum(
        jnp.square(moe_lib.moe_apply(p, cfg, x)[0]))))

    # measured per-device a2a traffic of the partitioned forward module
    hlo_a2a = collective_bytes(
        fwd.lower(p, x).compile().as_text()).get("all-to-all", 0)

    y = fwd(p, x)
    parity = float(jnp.max(jnp.abs(y - want)))
    fwd_s = _time(fwd, p, x, iters=iters)
    grad_s = _time(grad, p, x, iters=iters)
    # shape-static dispatch: repeated calls (incl. the timing loops above)
    # must not have grown the jit caches past the one warmup entry each
    recompiles = (fwd._cache_size() - 1) + (grad._cache_size() - 1)

    B, S, d = x.shape
    E = moe_lib.padded_experts(cfg.num_experts)
    xf = x.reshape(B * S, d)
    _, _, expert_idx = moe_lib._route(p, cfg, xf)
    stats = ep_dispatch_stats(expert_idx, E, ep, d,
                              jnp.dtype(x.dtype).itemsize)
    itemsize = jnp.dtype(x.dtype).itemsize
    cap = (B * S // ep) * cfg.top_k
    # the documented dense-emulation layout: rows out + expert ids + rows
    # back, each a (ep, cap, ...) exchange (kernels/moe/ep.py)
    expected_a2a = ep * cap * (2 * d * itemsize + 4)

    full = get_config(ARCH)
    return {
        "ep": ep,
        "parity_max_abs_err": parity,
        "fwd_s": fwd_s,
        "grad_s": grad_s,
        "recompiles_after_warmup": recompiles,
        "hlo_a2a_bytes": hlo_a2a,
        "hlo_a2a_expected_bytes": expected_a2a,
        "dispatch": stats,
        "full_analytic_train4k": ep_a2a_cost(
            full.replace(expert_parallel=ep), batch=8, seq=4096),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_moe_ep.json")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--quick", action="store_true",
                    help="fewer timing iterations (CI)")
    args = ap.parse_args()

    cfg0 = get_config(ARCH, reduced=True)
    p = initialize(moe_lib.moe_specs(cfg0), jax.random.PRNGKey(0), "float32")
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (args.batch, args.seq, cfg0.d_model)) * 0.5
    want = moe_lib.moe_apply_oracle(p, cfg0, x)

    rows = []
    for ep in EP_SWEEP:
        row = bench_ep(cfg0, p, x, want, ep, iters=2 if args.quick else 5)
        rows.append(row)
        d = row["dispatch"]
        print(f"[ep={ep}] parity {row['parity_max_abs_err']:.2e}  "
              f"fwd {row['fwd_s'] * 1e3:.1f} ms  grad {row['grad_s'] * 1e3:.1f} ms  "
              f"payload {d['payload_bytes_per_device'] / 2**20:.2f} MiB/dev  "
              f"off-device {d['offdevice_fraction']:.2f}  "
              f"hlo-a2a {row['hlo_a2a_bytes'] / 2**20:.2f} MiB  "
              f"recompiles {row['recompiles_after_warmup']}", flush=True)

    write_bench_json(args.out, "moe_ep", rows,
                     config=getattr(args, "arch", None))
    print(f"wrote {args.out}")

    bad = []
    base_payload = rows[0]["dispatch"]["payload_bytes_per_device"]
    for row in rows:
        ep = row["ep"]
        payload = row["dispatch"]["payload_bytes_per_device"]
        if row["parity_max_abs_err"] >= 1e-4:
            bad.append(f"ep={ep}: parity {row['parity_max_abs_err']:.2e}")
        if payload * ep != base_payload:
            bad.append(f"ep={ep}: payload {payload} not 1/EP of {base_payload}")
        if ep > 1 and row["hlo_a2a_bytes"] != row["hlo_a2a_expected_bytes"]:
            bad.append(f"ep={ep}: compiled a2a bytes {row['hlo_a2a_bytes']} "
                       f"!= documented layout {row['hlo_a2a_expected_bytes']}")
        if row["recompiles_after_warmup"] != 0:
            bad.append(f"ep={ep}: {row['recompiles_after_warmup']} recompiles")
    for msg in bad:
        print(f"[FAIL] {msg}")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
