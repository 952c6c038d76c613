"""Work counts from shapes, against numbers worked out by hand."""

import json

import numpy as np
import pytest
from conftest import ROOT

from benchmarks.chip import work


def config(name):
    return json.loads((ROOT / "benchmarks/chip/configs" / f"{name}.json")
                      .read_text())


def test_qwen_parameters():
    q = config("qwen1.5-0.5b")
    # q, k, v, o: 4 x 1024 x 1024; swiglu: 3 x 1024 x 2816
    assert work.layer_matmul_params(q) == 4 * 1024 * 1024 + 3 * 1024 * 2816
    # 24 layers of 12,845,056 plus the 1024 x 151,936 unembedding
    assert work.matmul_params(q) == 24 * 12_845_056 + 155_582_464
    # two norms and the q/k/v biases
    assert work.layer_small_params(q) == 2 * 1024 + 3 * 1024


def test_qwen_decode_step_by_hand():
    q = config("qwen1.5-0.5b")
    w = work.decode_step(q, 8, np.full(8, 1025))
    matmul = 24 * 12_845_056 + 155_582_464            # 463,863,808
    assert w["flops"] == 2 * matmul * 8 + 24 * 4 * 16 * 64 * 8 * 1025
    weights = (matmul + 24 * 5120 + 1024) * 2
    kv_row = 2 * 16 * 64 * 2                          # K and V, bf16
    assert w["bytes"] == (weights + 24 * kv_row * 8 * 1024
                          + 24 * kv_row * 8 + 8 * 1024 * 2
                          + 8 * 151_936 * 2)
    # about 0.93 GB of weights and 0.81 GB of cache at 1,024 positions
    assert 1.7e9 < w["bytes"] < 1.8e9


def test_qwen_prefill_by_hand():
    q = config("qwen1.5-0.5b")
    w = work.prefill(q, 8, 1024)
    layers = 24 * 12_845_056 * 2 * 8 * 1024
    attn = 24 * 8 * 4 * 16 * 64 * (1024 * 1025 // 2)
    unembed = 2 * 155_582_464 * 8
    assert w["flops"] == layers + attn + unembed
    assert w["flops"] == pytest.approx(5.47e12, rel=1e-2)

