"""The work each step needs, counted from a configuration's shapes.

These count what the algorithm requires, not what a compiled program
happens to do: weights are read once per step, a decode step reads the K
and V of the positions each sequence holds and writes one new row, and
prefill unembeds only the last position. Matmul FLOPs are 2 per
multiply-add. All weights are counted in the configuration's storage
dtype.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _bytes_of(cfg: Dict) -> int:
    return _ITEMSIZE[cfg["torch_dtype"]]


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one layer's projections and feed-forward."""
    M, F = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return M * q + 2 * M * kv + q * M + 3 * M * F


def layer_small_params(cfg: Dict) -> int:
    """Norm weights and Q/K/V biases of one layer."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * cfg["hidden_size"] + ((q + 2 * kv) if cfg["qkv_bias"] else 0)


def unembed_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: Dict) -> int:
    """Every weight a token multiplies: the layers' and the unembedding's
    (the embedding is a row lookup)."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + \
        unembed_params(cfg)


def _kv_row_bytes(cfg: Dict) -> int:
    """K and V of one position in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * _bytes_of(cfg)


def decode_step(cfg: Dict, batch: int, positions: np.ndarray) -> Dict:
    """One decode step of ``batch`` sequences; ``positions[b]`` is how many
    positions sequence b attends to in this step (its new one included)."""
    L, H, D = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
               cfg["head_dim"])
    ctx = int(np.sum(positions))
    flops = 2 * matmul_params(cfg) * batch + L * 4 * H * D * ctx
    weights = (matmul_params(cfg) + L * layer_small_params(cfg)
               + cfg["hidden_size"]) * _bytes_of(cfg)
    kv = L * _kv_row_bytes(cfg) * (ctx - batch)          # read what is held
    kv_new = L * _kv_row_bytes(cfg) * batch              # write the new row
    rows = batch * cfg["hidden_size"] * _bytes_of(cfg)   # embedding rows
    out = batch * cfg["vocab_size"] * _bytes_of(cfg)     # logits
    return {"flops": flops, "bytes": weights + kv + kv_new + rows + out}


def prefill(cfg: Dict, batch: int, prompt: int) -> Dict:
    """Causal prefill of ``batch`` prompts: every layer for every token,
    the unembedding for the last position only."""
    L, H, D = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
               cfg["head_dim"])
    tokens = batch * prompt
    attn = L * batch * 4 * H * D * (prompt * (prompt + 1) // 2)
    flops = (2 * L * layer_matmul_params(cfg) * tokens + attn
             + 2 * unembed_params(cfg) * batch)
    weights = (matmul_params(cfg) + L * layer_small_params(cfg)
               + cfg["hidden_size"]) * _bytes_of(cfg)
    nbytes = (weights + L * _kv_row_bytes(cfg) * tokens
              + tokens * cfg["hidden_size"] * _bytes_of(cfg))
    return {"flops": flops, "bytes": nbytes}

