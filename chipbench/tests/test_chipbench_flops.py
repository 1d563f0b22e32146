"""chipbench/flops.py against counts worked out by hand."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import flops

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def shape():
    hf = json.loads((ROOT / "chipbench/configs/qwen3-1.7b-L4.json").read_text())["hf_config"]
    return flops.LMShape.from_hf(hf, seq_len=1024)


def test_matmul_params(shape):
    # per layer: q, o 2048x2048; k, v 2048x1024; SwiGLU 3 x 2048x6144
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    assert per_layer == 50_331_648
    # four layers plus the tied head 2048 x 151936, counted once
    assert flops.lm_matmul_params(shape) == 4 * 50_331_648 + 311_164_928 == 512_491_520


def test_train_flops_per_token(shape):
    # 6 N, plus causal attention: 512.5 keys on average, q.k and p.v,
    # 16 heads of 128, 4 layers, forward and twice that backward
    attn_fwd = 4 * 2 * 2 * 512.5 * 16 * 128
    assert attn_fwd == 16_793_600
    assert flops.lm_train_flops_per_token(shape) == 6 * 512_491_520 + 3 * attn_fwd
    assert flops.lm_train_flops_per_token(shape) == 3_125_329_920


def test_extract_flops_per_token(shape):
    trunk = 2 * 4 * 50_331_648
    head = 4 * 2048 * 151_936  # logits, then (softmax - onehot) @ W^T
    assert flops.lm_extract_flops_per_token(shape) == trunk + 16_793_600 + head
    assert flops.lm_extract_flops_per_token(shape) == 1_664_106_496


def test_kernel_costs():
    f, b = flops.ce_proxy_cost(8192, 2048, 151_936)
    assert f == 4 * 8192 * 2048 * 151_936 == 10_196_252_360_704
    assert b == 1_244_659_712 + 134_217_728 + 32_768
    f, b = flops.fl_gains_argmax_cost(512, 512, 2048)
    assert f == 1_073_741_824
    assert b == 4 * 1024 * 2048 + 4 * 3072
    f, b = flops.fl_replay_cost(4096, 512, 2048)
    assert f == 8_589_934_592


def test_least_time_names_its_bound():
    assert flops.least_time_s(197e12, 1.0, 197e12, 819e9) == (1.0, "compute")
    t, bound = flops.least_time_s(1.0, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")
