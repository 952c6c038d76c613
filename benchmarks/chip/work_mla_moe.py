"""The work each step of a latent-attention MoE decoder (DeepSeek-V2)
needs, counted from its configuration file.

As in ``work``, these count what the algorithm requires: weights are
read once per step, in the storage dtype (the router in float32), and
the routed experts' weights are those of the experts the chip holds; a
decode step reads the latent rows (``kv_lora_rank + qk_rope_head_dim``
values) each sequence holds and writes one, with attention absorbed into
the latent space; prefill expands keys and values per head and unembeds
only the last position. Routed-expert FLOPs follow the assignments to
held experts, which the program counts (``moe.assign_held.*``). Matmul
FLOPs are 2 per multiply-add.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _bytes_of(cfg: Dict) -> int:
    return _ITEMSIZE[cfg["torch_dtype"]]


def attention_params(cfg: Dict) -> int:
    """One layer's MLA projections: wq, wkv_a, wk_b, wv_b, wo."""
    M, H, R = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return M * H * (dn + dr) + M * (R + dr) + R * H * (dn + dv) + H * dv * M


def expert_params(cfg: Dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layers(cfg: Dict):
    """(all layers, dense layers, MoE layers)."""
    L, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return L, nd, L - nd


def matmul_params(cfg: Dict) -> int:
    """Every weight each token multiplies, whatever its routing: the
    projections, the dense layers', shared experts' and router's weights
    and the unembedding."""
    L, nd, nm = _layers(cfg)
    M = cfg["hidden_size"]
    return (L * attention_params(cfg)
            + nd * 3 * M * cfg["intermediate_size"]
            + nm * (cfg["n_shared_experts"] * expert_params(cfg)
                    + M * cfg["router_outputs"])
            + M * cfg["vocab_size"])


def weight_bytes(cfg: Dict, experts_held: int) -> int:
    """Every weight a step reads once: the matmul weights above, the held
    experts', the norms; the router is float32."""
    L, nd, nm = _layers(cfg)
    M, b = cfg["hidden_size"], _bytes_of(cfg)
    router = nm * M * cfg["router_outputs"]
    norms = L * (2 * M + cfg["kv_lora_rank"]) + M
    return ((matmul_params(cfg) - router + nm * experts_held
             * expert_params(cfg) + norms) * b + router * 4)


def _latent_row_bytes(cfg: Dict) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * _bytes_of(cfg)


def decode_step(cfg: Dict, batch: int, positions: np.ndarray,
                experts_held: int, assign_held: float) -> Dict:
    """One decode step of ``batch`` sequences; ``positions[b]`` is how many
    positions sequence b attends to in this step (its new one included);
    ``assign_held`` the step's top-k assignments to held experts, over
    all MoE layers."""
    L = cfg["num_hidden_layers"]
    H, R, dr = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["qk_rope_head_dim"])
    ctx = int(np.sum(positions))
    # scores against the latent and rope rows, then w · latent
    attn = L * 2 * H * (2 * R + dr) * ctx
    flops = (2 * matmul_params(cfg) * batch + attn
             + 2 * expert_params(cfg) * assign_held)
    rows = L * _latent_row_bytes(cfg)
    nbytes = (weight_bytes(cfg, experts_held) + rows * (ctx - batch)
              + rows * batch                       # the new rows
              + batch * cfg["hidden_size"] * _bytes_of(cfg)
              + batch * cfg["vocab_size"] * _bytes_of(cfg))
    return {"flops": flops, "bytes": nbytes}


def prefill(cfg: Dict, batch: int, prompt: int, experts_held: int,
            assign_held: float) -> Dict:
    """Causal prefill of ``batch`` prompts: every layer for every token,
    keys and values expanded per head, the unembedding for the last
    position only; ``assign_held`` the prefill's assignments to held
    experts, over all MoE layers."""
    L = cfg["num_hidden_layers"]
    M, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    tokens = batch * prompt
    attn = L * batch * 2 * H * (dn + dr + dv) * (prompt * (prompt + 1) // 2)
    per_token = matmul_params(cfg) - M * cfg["vocab_size"]
    flops = (2 * per_token * tokens + attn
             + 2 * expert_params(cfg) * assign_held
             + 2 * M * cfg["vocab_size"] * batch)
    nbytes = (weight_bytes(cfg, experts_held)
              + L * _latent_row_bytes(cfg) * tokens
              + tokens * M * _bytes_of(cfg))
    return {"flops": flops, "bytes": nbytes}


def job_count(job, name: str) -> Optional[float]:
    """A counter of a recorded job (``repro.trace``), on whichever of
    its spans holds it, or None."""
    for record in job.spans:
        if record.counts and name in record.counts:
            return record.counts[name]
    return None
