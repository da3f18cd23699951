"""Discovery by name, refusal off a TPU, and the hand counts."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import bench, counts

ROOT = bench.ROOT
SPEC = json.load(open(ROOT / "BENCHMARK.json"))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(cell):
    c = bench.load_cell(cell)
    assert c.traffic["kind"] in ("train", "serve")
    assert (bench.HERE / "kinds" / f"{c.traffic['kind']}.py").exists()
    assert (bench.HERE / "reference" / f"{c.config['reference']}.py").exists()
    assert set(c.limits["checks"])
    for m in c.per_layer:
        assert hasattr(bench.module("metrics", m["name"]), "read")
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}


def test_every_per_layer_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert (bench.HERE / "metrics" / f"{m['name']}.py").exists()


def test_config_files_state_what_the_program_runs():
    from chipbench.kinds.train import program_config
    for c in SPEC["configs"]:
        cfg = json.load(open(ROOT / c["file"]))
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        program_config(cfg)          # raises on any size that differs


def test_refuses_a_cpu_device():
    cell = SPEC["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        cell, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_qwen_layer_hand_count():
    cfg = json.load(open(bench.HERE / "configs" / "qwen2-moe-a2.7b-l2.json"))
    d, half = 2048, 1024
    adapters = 2 * (2 * half * d + d * half) + 2 * (half * d + d * half)
    attn = 2 * (4 * d * d)                    # 16 heads x 128, kv 16
    router = 2 * d * 60
    experts = 4 * 3 * 2 * d * 1408
    shared = 3 * 2 * d * 5632 + 2 * d
    assert counts.layer_matmul_flops(cfg, "moe") == adapters + attn + router \
        + experts + shared
    # causal mean over 8192 positions: (8192 + 1) / 2 keys
    assert counts.attended_keys(8192) == 4096.5
    per_tok = 2 * (adapters + attn + router + experts + shared
                   + 4 * 16 * 128 * 4096.5) + 2 * d * 151936
    assert counts.forward_flops_per_token(cfg, 8192) == per_tok


def test_danube_layer_hand_count():
    cfg = json.load(open(bench.HERE / "configs" / "h2o-danube-1.8b-l4.json"))
    d, half = 2560, 1280
    adapters = 2 * (2 * half * d + d * half) + 2 * (half * d + d * half)
    attn = 2 * (d * 2560 + 2 * d * 640 + 2560 * d)     # 32 x 80, kv 8 x 80
    mlp = 3 * 2 * d * 6912
    assert counts.layer_matmul_flops(cfg, "dense") == adapters + attn + mlp
    # window 4096 covers a 4096 row: plain causal
    assert counts.attended_keys(4096, 4096) == 2048.5
    # a 6000-token row under the window: 4096 x 4097 / 2 + 1904 x 4096
    assert counts.attended_keys(6000, 4096) == (
        4096 * 4097 / 2 + 1904 * 4096) / 6000


def test_flash_and_gmm_counts():
    f, b = counts.gmm_call(rows=8, k=4, n=2, experts=3)
    assert f == 2 * 8 * 4 * 2 and b == 2 * (8 * 4 + 3 * 4 * 2 + 8 * 2)
    f, _ = counts.flash_call("fwd", 1, 2, 2, 4, 8, 8)
    assert f == 2 * 2 * 1 * 2 * 8 * (4 * 5 / 2)
