"""chip_smoke.py's phases at reduced size on CPU.

The script itself refuses to run off a TPU; its phase functions take a
``ModelConfig`` so the same code runs here on smoke-sized configs (kernels
in interpret mode, or their ``impl="jax"`` paths).
"""
import pathlib
import sys

import pytest

from repro.configs.base import get_config
from repro.optim.adamw import AdamW
from repro.optim.lomo import LoMo

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke as cs  # noqa: E402


def _moe():
    return get_config("qwen2-moe-a2.7b", reduced=True).replace(
        num_layers=2, use_flash_kernel=True, moe_backend="grouped")


def _dense(num_layers=2):
    return get_config("h2o-danube-1.8b", reduced=True).replace(
        num_layers=num_layers)


def test_main_refuses_cpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""                      # no result line at all
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("phase", ["A", "B"])
def test_train_phase_reduced(phase):
    # learning rates for the reduced widths: a 4-step fall must beat the
    # loss spread of 2 x 64-token batches
    if phase == "A":
        out = cs.phase_train(_moe(), LoMo(lr=1.0), batch=2, seq=64,
                             steps=4, require_kernels=False)
    else:
        out = cs.phase_train(_dense(), AdamW(lr=1e-3), batch=2,
                             seq=128, steps=4, require_kernels=False)
    assert out["failed"] == [], out
    assert len(out["losses"]) == 4
    assert out["losses"][-1] < out["losses"][0]
    assert not out["tpu_custom_call"]         # CPU: no Mosaic kernel
    assert out["step_bytes"]["argument"] > 0


def test_train_phase_flags_missing_kernels():
    """On a TPU the MoE phase demands the Mosaic kernels; a CPU compile has
    none, so the same call with ``require_kernels`` must fail that check."""
    out = cs.phase_train(_moe().replace(num_layers=1), LoMo(lr=1.0),
                         batch=1, seq=64, steps=2, require_kernels=True)
    assert "tpu_custom_call" in out["failed"]


def test_serve_phase_reduced():
    out = cs.phase_serve(_dense(), slots=4, requests=6, shared_prefix=16,
                         min_prompt=8, max_prompt=40, gen=8)
    assert out["failed"] == [], out
    assert out["requests_with_all_tokens"] == 6
    assert out["recompiles_post_warmup"] == 0


def test_kernel_phase_reduced():
    out = cs.phase_kernels(_moe(), _dense(), batch=1, seq=128, slots=4,
                           kv_len=64)
    assert out["failed"] == [], out
    assert set(out["rel_err"]) == {"flash_out", "flash_dq", "flash_dk",
                                   "flash_dv", "gmm_out", "gmm_dlhs",
                                   "gmm_drhs", "paged_out"}
    # CPU runs the kernels in interpret mode in f32: far inside the bf16
    # tolerance the chip is held to
    assert max(out["rel_err"].values()) < 1e-5
