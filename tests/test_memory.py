"""Memory planner subsystem: estimator accuracy (static trace vs concrete
bytes, within the 10% contract), planner budget/ordering behaviour, and the
offload wrapper's gradient round-trip against the store-everything baseline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.reversible import POLICIES, mixed_policy_stack, policy_segments
from repro.memory import estimator as est_mod
from repro.memory import offload as off_mod
from repro.memory.estimator import GiB
from repro.memory.planner import plan
from repro.models.model import Model

jax.config.update("jax_platform_name", "cpu")

N_LAYERS = 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("h2o-danube-1.8b", reduced=True).replace(
        num_layers=N_LAYERS)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, cfg.vocab_size)}
    return cfg, model, params, batch


def _measured_residual_bytes(model, params, batch, save_memory):
    _, vjp_fn = jax.vjp(
        lambda p: model.loss(p, batch, save_memory=save_memory), params)
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(vjp_fn)
               if hasattr(x, "size"))


# ------------------------------------------------------------- estimator

def test_param_and_opt_bytes_exact(setup):
    cfg, model, params, _ = setup
    est = est_mod.estimate(cfg, 2, 32, optimizer="adamw")
    actual_params = est_mod.array_bytes(params)
    assert est.param_bytes == actual_params
    from repro.optim.adamw import AdamW
    actual_opt = est_mod.array_bytes(AdamW(lr=1e-4).init(params))
    assert est.opt_bytes == actual_opt


@pytest.mark.parametrize("policy", POLICIES)
def test_residual_bytes_static_matches_concrete(setup, policy):
    """The static (eval_shape) trace must equal concrete jax.vjp bytes —
    well inside the 10% estimator-vs-actual contract."""
    cfg, model, params, batch = setup
    sm = [policy] * N_LAYERS
    predicted = est_mod.residual_bytes(model, 2, 32, save_memory=sm)
    measured = _measured_residual_bytes(model, params, batch, sm)
    assert abs(predicted - measured) <= 0.10 * measured
    assert predicted == measured          # trace-level: exactly equal


def test_per_unit_linear_model_within_10pct(setup):
    """fixed + n*unit must reproduce the directly traced n-layer total."""
    cfg, model, params, batch = setup
    est = est_mod.estimate(cfg, 2, 32, optimizer="adamw")
    for policy in ("store", "remat"):
        predicted = (est.param_bytes + est.fixed_act_for([policy])
                     + N_LAYERS * est.unit_act_bytes[policy]
                     + N_LAYERS * est.unit_host_bytes[policy])
        direct = est_mod.residual_bytes(model, 2, 32,
                                        save_memory=[policy] * N_LAYERS)
        assert abs(predicted - direct) <= 0.10 * direct, (policy, predicted,
                                                          direct)


def test_policy_memory_ordering(setup):
    """reversible <= remat < store, and offload device bytes < remat's."""
    cfg, *_ = setup
    est = est_mod.estimate(cfg, 2, 32, optimizer="adamw")
    ua = est.unit_act_bytes
    assert ua["reversible"] <= ua["remat"] < ua["store"]
    assert ua["offload"] < ua["remat"]
    assert est.unit_host_bytes["offload"] > 0
    assert est.unit_host_bytes["store"] == 0


def test_optimizer_state_modeling(setup):
    cfg, *_ = setup
    adamw = est_mod.estimate(cfg, 2, 32, optimizer="adamw")
    lomo = est_mod.estimate(cfg, 2, 32, optimizer="lomo")
    assert lomo.opt_bytes < adamw.opt_bytes / 100     # LoMo: ~zero state
    assert lomo.grad_bytes <= adamw.grad_bytes        # donated update buffer


def test_fixed_act_is_policy_aware(setup):
    """The linear model's depth-free term must track the plan's policies:
    an all-reversible plan's linear total stays within 10% of its trace."""
    cfg, model, params, batch = setup
    est = est_mod.estimate(cfg, 2, 32, optimizer="adamw")
    lin = (est.device_total(["reversible"] * N_LAYERS)
           - est.param_bytes - est.grad_bytes - est.opt_bytes)
    traced = est_mod.residual_bytes(
        model, 2, 32, save_memory=["reversible"] * N_LAYERS) - est.param_bytes
    assert abs(lin - traced) <= 0.10 * max(traced, 1), (lin, traced)


def test_attention_backward_cost_flash_transients_flat_in_seq():
    """Flash backward transients are the VMEM tile working set — they must
    NOT scale with S^2 (or S at all once S >= the block sizes), while the
    dense-ref recompute quadruples when S doubles; flash residuals stay
    linear in S and the gate quantity (flash transient < dense transient)
    holds at the benchmark's S=1024."""
    cfg = get_config("h2o-danube-1.8b")
    c1 = est_mod.attention_backward_cost(cfg, batch=8, seq=1024)
    c2 = est_mod.attention_backward_cost(cfg, batch=8, seq=2048)
    assert c1["flash"]["transient_bytes"] == c2["flash"]["transient_bytes"]
    assert c2["dense"]["transient_bytes"] == 4 * c1["dense"]["transient_bytes"]
    assert c2["flash"]["residual_bytes"] == 2 * c1["flash"]["residual_bytes"]
    assert c1["flash"]["transient_bytes"] < c1["dense"]["transient_bytes"]


def test_attention_backward_cost_surfaces_in_plan_report():
    cfg = get_config("h2o-danube-1.8b", reduced=True)
    p = plan(cfg, budget_gb=1000.0, batch=2, seq=32, optimizer="adamw",
             trace_check=False)
    assert p.attn_bwd is not None
    assert "attn backward/layer" in p.report()
    # attention-free families carry no attention line
    cfg_ssm = get_config("rwkv6-3b", reduced=True)
    p_ssm = plan(cfg_ssm, budget_gb=1000.0, batch=2, seq=32,
                 optimizer="adamw", trace_check=False)
    assert p_ssm.attn_bwd is None
    assert "attn backward/layer" not in p_ssm.report()


def test_encdec_policy_list_covers_decoder_only():
    """On enc-dec configs a policy list plans the decoder; the encoder keeps
    the O(1) reversible default (it must NOT silently absorb the list)."""
    cfg = get_config("whisper-medium", reduced=True)
    m = Model(cfg)
    n = sum(s.n for s in m.stacks if s.role == "main")
    r_store = est_mod.residual_bytes(m, 2, 16, save_memory=["store"] * n)
    r_rev = est_mod.residual_bytes(m, 2, 16, save_memory=True)
    assert r_store > r_rev


# ------------------------------------------------------------- planner

def test_planner_generous_budget_stores_everything(setup):
    cfg, *_ = setup
    p = plan(cfg, budget_gb=1000.0, batch=2, seq=32, optimizer="adamw")
    assert p.fits
    assert p.policies == ["store"] * N_LAYERS


def test_planner_tight_budget_prefers_reversible(setup):
    """Just below the all-store requirement the planner must flip to the
    preferred recompute policy (reversible here), not offload."""
    cfg, *_ = setup
    est = est_mod.estimate(cfg, 2, 32, optimizer="adamw")
    store_total = est.device_total(["store"] * N_LAYERS)
    p = plan(cfg, budget_gb=(store_total - 1) / GiB, batch=2, seq=32,
             optimizer="adamw", estimate=est)
    assert p.fits
    assert "reversible" in p.policies
    assert "offload" not in p.policies
    assert p.device_bytes <= p.budget_bytes


def test_planner_impossible_budget_reports_unfit(setup):
    cfg, *_ = setup
    p = plan(cfg, budget_gb=1e-6, batch=2, seq=32, optimizer="adamw")
    assert not p.fits
    # last resort reached: everything offloaded
    assert p.policies == ["offload"] * N_LAYERS
    report = p.report()
    assert "DOES NOT FIT" in report and "lomo" in report


def test_planner_remat_for_non_reversible(setup):
    cfg, *_ = setup
    cfg_std = cfg.replace(reversible=False)
    est = est_mod.estimate(cfg_std, 2, 32, optimizer="adamw")
    store_total = est.device_total(["store"] * N_LAYERS)
    p = plan(cfg_std, budget_gb=(store_total - 1) / GiB, batch=2, seq=32,
             optimizer="adamw", estimate=est)
    assert "reversible" not in p.policies
    assert "remat" in p.policies


def test_report_lists_every_segment(setup):
    cfg, *_ = setup
    p = plan(cfg, budget_gb=1000.0, batch=2, seq=32, optimizer="adamw")
    rep = p.report()
    assert "store" in rep and "FITS" in rep and cfg.name in rep


def test_grouped_moe_backend_residuals_below_einsum_and_plan_fits():
    """Grouped dispatch (repro.kernels.moe) must shrink the backward
    residuals the planner budgets for: its store-everything trace stays
    below the einsum path's, and a budget sized to the grouped trace still
    yields a fitting plan."""
    cfg = get_config("qwen2-moe-a2.7b", reduced=True).replace(num_layers=2)
    n = 2
    r_einsum = est_mod.residual_bytes(Model(cfg), 2, 256,
                                      save_memory=["store"] * n)
    cfg_g = cfg.replace(moe_backend="grouped")
    r_grouped = est_mod.residual_bytes(Model(cfg_g), 2, 256,
                                       save_memory=["store"] * n)
    assert r_grouped < r_einsum, (r_grouped, r_einsum)

    # a budget the einsum trace cannot meet all-store still fits grouped
    budget_gb = (r_grouped + 256 * 2**20) / GiB
    p = plan(cfg_g, budget_gb=budget_gb, batch=2, seq=256,
             optimizer="lomo")
    assert p.fits
    assert p.device_bytes <= p.budget_bytes


# ------------------------------------------------------------- mixed stack

def test_policy_segments_grouping():
    segs = policy_segments(["store", "store", "remat", "offload", "offload"])
    assert segs == [(0, 2, "store"), (2, 3, "remat"), (3, 5, "offload")]
    with pytest.raises(AssertionError):
        policy_segments(["bogus"])


def test_mixed_policy_forward_identical(setup):
    cfg, model, params, batch = setup
    base = model.loss(params, batch, save_memory=False)
    for sm in (["store"] * 4, ["remat"] * 4, ["offload"] * 4,
               ["offload", "reversible", "remat", "store"]):
        np.testing.assert_allclose(
            np.asarray(model.loss(params, batch, save_memory=sm)),
            np.asarray(base), rtol=1e-6)


def test_offload_gradients_match_store_baseline(setup):
    """The issue's 1e-5 contract: offload must round-trip gradients against
    the store-everything baseline (both are exact AD — no fixed point)."""
    cfg, model, params, batch = setup
    g_store = jax.grad(
        lambda p: model.loss(p, batch, save_memory=["store"] * 4))(params)
    g_off = jax.grad(
        lambda p: model.loss(p, batch, save_memory=["offload"] * 4))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_off),
                    jax.tree_util.tree_leaves(g_store)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-5)


def test_mixed_policies_gradients_close_to_baseline(setup):
    """Mixed plan incl. the fixed-point reversible segment: rel error stays
    within the reversible stack's own tolerance."""
    cfg, model, params, batch = setup
    g_base = jax.grad(
        lambda p: model.loss(p, batch, save_memory=False))(params)
    g_mix = jax.grad(lambda p: model.loss(
        p, batch, save_memory=["offload", "reversible", "remat", "store"]))(params)

    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b)) / (1e-6 + jnp.max(jnp.abs(b))))
    worst = max(rel(a, b) for a, b in zip(jax.tree_util.tree_leaves(g_mix),
                                          jax.tree_util.tree_leaves(g_base)))
    assert worst < 5e-3


def test_mixed_policy_jits(setup):
    cfg, model, params, batch = setup
    sm = ["offload", "reversible", "remat", "store"]
    step = jax.jit(lambda p, b: model.loss(p, b, save_memory=sm))
    assert bool(jnp.isfinite(step(params, batch)))


def test_std_path_mixed_policies(setup):
    """Non-reversible configs take the _std_mixed path (no reversible)."""
    cfg, *_ , batch = setup
    cfg_std = cfg.replace(reversible=False)
    m = Model(cfg_std)
    params = m.init(jax.random.PRNGKey(0))
    base = jax.grad(lambda p: m.loss(p, batch, save_memory=False))(params)
    mixed = jax.grad(lambda p: m.loss(
        p, batch, save_memory=["offload", "remat", "store", "remat"]))(params)
    for a, b in zip(jax.tree_util.tree_leaves(mixed),
                    jax.tree_util.tree_leaves(base)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


# ------------------------------------------------------------- offload plumbing

def test_offload_noop_on_cpu_backend():
    """This container's CPU backend has no distinct host memory: the
    transfer helpers must degrade to identity, never crash."""
    assert off_mod.host_memory_kind() is None
    x = jnp.ones((4, 4))
    assert off_mod.to_host(x) is x
    assert off_mod.to_device(x) is x


def test_train_step_accepts_plan(setup):
    """driver/trainer plumbing: a policy list flows through make_train_step."""
    from repro.optim.adamw import AdamW
    from repro.train.trainer import make_train_step
    cfg, model, params, batch = setup
    opt = AdamW(lr=1e-4)
    step = jax.jit(make_train_step(
        model, opt, save_memory=["offload", "reversible", "remat", "store"]))
    p2, st2, metrics = step(params, opt.init(params), batch)
    assert bool(jnp.isfinite(metrics["loss"]))


class _Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v5 lite"),
                                           ("cpu", "cpu"),
                                           ("tpu", "TPU v4")])
def test_device_peaks_keyed_by_kind(platform, kind):
    """Peaks come from the published table by device kind; a CPU or an
    accelerator missing from it is an error, never a guessed figure."""
    dev = _Device(platform, kind)
    if kind in est_mod.DEVICE_PEAKS:
        peaks = est_mod.device_peaks(dev)
        assert peaks.bf16_flops_per_s == 197e12
        assert peaks.hbm_bytes_per_s == 819e9
        assert "TPU v5e" in peaks.source
    else:
        with pytest.raises(ValueError, match="no published peaks"):
            est_mod.device_peaks(dev)
