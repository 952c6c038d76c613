"""Multi-head Latent Attention (DeepSeek-V2): compressed KV cache.

The KV cache stores only the low-rank latent ``c_kv`` (kv_lora_rank) plus
the decoupled RoPE key ``k_pe`` — 576 values/token for V2-Lite instead of
16 heads × 2 × 128. The rope part follows DeepSeek: its pairs are
interleaved (x[2i], x[2i+1]), its frequencies and softmax scale follow
the config's YaRN ``rope_scaling``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import NEG_INF, flash_attention_jnp, write_row
from .layers import SpecTree, apply_rope, param, rms_norm, yarn_mscale


def softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times mscale(factor, mscale_all_dim)²
    under YaRN."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    yarn = cfg.rope_scaling
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def _rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """DeepSeek's interleaved rope: the pairs (x[2i], x[2i+1]) are moved
    to (i, i + D/2) and turned in rotate-half form; q and k move alike,
    so their products are the interleaved form's."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling)


def init_mla(key: jax.Array, cfg: ModelConfig, specs: SpecTree) -> Dict:
    sub = specs.sub("mla")
    ks = jax.random.split(key, 6)
    M, H = cfg.d_model, cfg.num_heads
    R, dr, dn, dv = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    return {
        # queries: full-rank (V2-Lite has no q compression)
        "wq": param(ks[0], (M, H * (dn + dr)), ("embed", "q_flat"), sub, "wq"),
        # KV path: down-projection to latent + decoupled rope key
        "wkv_a": param(ks[1], (M, R + dr), ("embed", "lora"), sub, "wkv_a"),
        "kv_norm": param(ks[2], (R,), ("lora",), sub, "kv_norm", scale=0.0) + 1.0,
        # up-projections from latent
        "wk_b": param(ks[3], (R, H * dn), ("lora", "q_flat"), sub, "wk_b"),
        "wv_b": param(ks[4], (R, H * dv), ("lora", "q_flat"), sub, "wv_b"),
        "wo": param(ks[5], (H * dv, M), ("q_flat", "embed"), sub, "wo"),
    }


def _mla_qkv(p: Dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    B, S, _ = x.shape
    H = cfg.num_heads
    R, dr, dn, dv = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    q = jnp.einsum("bsm,mh->bsh", x, p["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = _rope(q_pe, positions, cfg)
    kv = jnp.einsum("bsm,mr->bsr", x, p["wkv_a"])
    c_kv, k_pe = kv[..., :R], kv[..., R:]
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_pe = _rope(k_pe, positions, cfg)                       # (B,S,dr)
    return q_nope, q_pe, c_kv, k_pe


def _expand_kv(p: Dict, c_kv: jax.Array, cfg: ModelConfig):
    B, S, R = c_kv.shape
    H, dn, dv = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim
    k_nope = jnp.einsum("bsr,rh->bsh", c_kv, p["wk_b"]).reshape(B, S, H, dn)
    v = jnp.einsum("bsr,rh->bsh", c_kv, p["wv_b"]).reshape(B, S, H, dv)
    return k_nope, v


def mla_train(p: Dict, x: jax.Array, cfg: ModelConfig,
              positions: jax.Array, return_kv: bool = False):
    B, S, _ = x.shape
    H, dv = cfg.num_heads, cfg.v_head_dim
    q_nope, q_pe, c_kv, k_pe = _mla_qkv(p, x, cfg, positions)
    k_nope, v = _expand_kv(p, c_kv, cfg)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None, :], (B, S, H, cfg.qk_rope_dim))], axis=-1)
    # pad v head_dim up to qk dim for the shared flash path, slice after
    pad = q.shape[-1] - dv
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    out = flash_attention_jnp(q, k, v_p, causal=True,
                              scale=softmax_scale(cfg))[..., :dv]
    out = out.reshape(B, S, H * dv)
    y = jnp.einsum("bsh,hm->bsm", out, p["wo"])
    if not return_kv:
        return y
    return y, {"c_kv": c_kv.astype(jnp.bfloat16),
               "k_pe": k_pe.astype(jnp.bfloat16)}


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_pe": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def mla_cache_specs() -> Dict:
    # "kv_lora" (≠ weights' replicated "lora") lets the latent cache shard
    # over the model axis: 130 GB of decode_32k cache → 0.5 GB/device.
    return {"c_kv": ("layers", "batch", "kv_seq", "kv_lora"),
            "k_pe": ("layers", "batch", "kv_seq", None)}


def mla_decode(p: Dict, x: jax.Array, cache: Dict, layer: jax.Array,
               cfg: ModelConfig, cur_index: jax.Array
               ) -> Tuple[jax.Array, Dict]:
    """Absorbed-matmul MLA decode: attend in the latent space.

    cache: the layer-stacked latent cache; the new row is written into
    the stack at ``layer`` and the layer's slab is read where it lies.
    Scores: q_nope·W_kb (absorb) against cached c_kv; rope part separate.
    Memory roofline per token = R + dr bytes, not H·(dn+dv).
    """
    B = x.shape[0]
    H, R = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    S = cache["c_kv"].shape[2]
    q_nope, q_pe, c_new, kpe_new = _mla_qkv(p, x, cfg, cur_index[:, None])
    cache = {"c_kv": write_row(cache["c_kv"], layer, cur_index, c_new[:, 0]),
             "k_pe": write_row(cache["k_pe"], layer, cur_index,
                               kpe_new[:, 0])}
    c_kv, k_pe = cache["c_kv"][layer], cache["k_pe"][layer]

    wk_b = p["wk_b"].reshape(R, H, dn)
    wv_b = p["wv_b"].reshape(R, H, dv)
    # absorb W_kb into the query: q_lat (B,H,R)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    s = jnp.einsum("bhr,bsr->bhs", q_lat, c_kv.astype(jnp.float32))
    s += jnp.einsum("bhd,bsd->bhs", q_pe[:, 0].astype(jnp.float32),
                    k_pe.astype(jnp.float32))
    s *= softmax_scale(cfg)
    valid = jnp.arange(S)[None, :] <= cur_index[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, c_kv.astype(jnp.float32))  # latent
    out = jnp.einsum("bhr,rhd->bhd", o_lat, wv_b.astype(jnp.float32))
    out = out.reshape(B, 1, H * dv).astype(x.dtype)
    y = jnp.einsum("bsh,hm->bsm", out, p["wo"])
    return y, cache
