"""GQA attention: chunked (flash-style) training/prefill path + decode path.

The training path is a pure-jnp online-softmax implementation (nested scan
over query/key blocks) so the full S×S score matrix is never materialized —
required for prefill_32k to fit HBM. The Pallas kernel in
``repro.kernels.flash_attention`` is the TPU drop-in with the same oracle.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import SpecTree, apply_rope, param

NEG_INF = -1e30


def init_attention(key: jax.Array, cfg: ModelConfig, specs: SpecTree) -> Dict:
    sub = specs.sub("attn")
    ks = jax.random.split(key, 8)
    H, Kh, D, M = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": param(ks[0], (M, H * D), ("embed", "q_flat"), sub, "wq"),
        "wk": param(ks[1], (M, Kh * D), ("embed", "kv_flat"), sub, "wk"),
        "wv": param(ks[2], (M, Kh * D), ("embed", "kv_flat"), sub, "wv"),
        "wo": param(ks[3], (H * D, M), ("q_flat", "embed"), sub, "wo"),
    }
    if cfg.qkv_bias:
        p["bq"] = param(ks[4], (H * D,), ("q_flat",), sub, "bq", scale=0.0)
        p["bk"] = param(ks[5], (Kh * D,), ("kv_flat",), sub, "bk", scale=0.0)
        p["bv"] = param(ks[6], (Kh * D,), ("kv_flat",), sub, "bv", scale=0.0)
    return p


def qkv_proj(p: Dict, x: jax.Array, cfg: ModelConfig,
             positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    H, Kh, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsm,mh->bsh", x, p["wq"])
    k = jnp.einsum("bsm,mh->bsh", x, p["wk"])
    v = jnp.einsum("bsm,mh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, D)
    k = k.reshape(B, S, Kh, D)
    v = v.reshape(B, S, Kh, D)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def flash_attention_jnp(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = True, window: Optional[int] = None,
    q_block: int = 512, kv_block: int = 512,
    q_offset: int = 0, scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention.

    q: (B, Sq, H, D); k, v: (B, Skv, Kh, D) with H a multiple of Kh.
    Never materializes more than (q_block × kv_block) scores per (B, head).
    ``q_offset`` positions q tokens at ``q_offset + i`` against kv.
    Scores are scaled by ``scale`` (default D^-0.5). Operands are read
    in float32.
    """
    B, Sq, H, D = q.shape
    _, Skv, Kh, _ = k.shape
    G = H // Kh
    scale = D ** -0.5 if scale is None else scale

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    # pad to block multiples
    Sq_p = -(-Sq // q_block) * q_block
    Skv_p = -(-Skv // kv_block) * kv_block
    qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))

    nq, nkv = Sq_p // q_block, Skv_p // kv_block
    # (nq, B, qb, Kh, G, D)
    qb = qp.reshape(B, nq, q_block, Kh, G, D).transpose(1, 0, 2, 3, 4, 5)
    kb = kp.reshape(B, nkv, kv_block, Kh, D).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nkv, kv_block, Kh, D).transpose(1, 0, 2, 3, 4)

    q_pos_base = jnp.arange(q_block)
    kv_pos_base = jnp.arange(kv_block)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk                     # index scalar, (B,qb,Kh,G,D)
        q_pos = q_offset + qi * q_block + q_pos_base          # (qb,)
        qc = qblk.astype(jnp.float32)

        def kv_step(carry, kj_blk):
            m, l, acc = carry
            kj, kblk, vblk = kj_blk
            kv_pos = kj * kv_block + kv_pos_base              # (kb,)
            s = jnp.einsum("bqkgd,btkd->bkgqt", qc,
                           kblk.astype(jnp.float32),
                           preferred_element_type=jnp.float32) * scale
            mask = kv_pos[None, :] <= (Skv - 1)  # kv padding
            if causal:
                mask = mask & (kv_pos[None, :] <= q_pos[:, None])
            if window is not None:
                mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, vblk.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Kh, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kh, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Kh, G, q_block, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nkv), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, Kh, G, qb, D) -> (B, qb, Kh, G, D)
        return None, out.transpose(0, 3, 1, 2, 4)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    # (nq, B, qb, Kh, G, D) -> (B, Sq_p, H, D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_p, H, D)
    return out[:, :Sq].astype(q.dtype)


def attention_train(p: Dict, x: jax.Array, cfg: ModelConfig,
                    positions: jax.Array, return_kv: bool = False):
    q, k, v = qkv_proj(p, x, cfg, positions)
    out = flash_attention_jnp(q, k, v, causal=True, window=cfg.window)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    y = jnp.einsum("bsh,hm->bsm", out, p["wo"])
    if not return_kv:
        return y
    # flat-layout cache piece for decode continuation. A sliding-window
    # arch keeps the last `window` positions in decode's ring order:
    # position t at slot t % window
    Kh, D = cfg.num_kv_heads, cfg.head_dim
    W = cfg.window
    if W is not None and S > W:
        k = jnp.roll(k[:, -W:], S % W, axis=1)
        v = jnp.roll(v[:, -W:], S % W, axis=1)
    return y, {"k": k.reshape(B, -1, Kh * D).astype(jnp.bfloat16),
               "v": v.reshape(B, -1, Kh * D).astype(jnp.bfloat16)}


# ---------------------------------------------------------------------------
# decode (one token, contiguous KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Dict:
    """KV cache stored FLAT (B, S, Kh·D): the flattened feature dim is
    divisible by the model axis for every assigned arch even when Kh is not
    (command-r/qwen2.5/llava have Kh=8 < 16; hymba Kh=5), so tensor-parallel
    cache sharding never falls back to replication."""
    if cfg.window is not None:
        max_len = min(max_len, cfg.window)
    Kh, D = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, Kh * D), dtype),
        "v": jnp.zeros((batch, max_len, Kh * D), dtype),
    }


def kv_cache_specs() -> Dict:
    return {"k": ("layers", "batch", "kv_seq", "kv_flat"),
            "v": ("layers", "batch", "kv_seq", "kv_flat")}


def write_row(stack: jax.Array, layer: jax.Array, slot: jax.Array,
              row: jax.Array) -> jax.Array:
    """Sequence b's new row ``row[b]`` written at ``(layer, b, slot[b])``
    of a layer-stacked cache leaf ``(L, B, S, F)``: a scatter of B rows,
    which XLA does in place, never a copy of a layer's slab."""
    b_idx = jnp.arange(stack.shape[1])
    return stack.at[layer, b_idx, slot].set(row.astype(stack.dtype))


def block_diagonal(q: jax.Array) -> jax.Array:
    """q (B, Kh, G, D) as (B, Kh·D, Kh·G), zero outside each KV head's
    block: row kk·D + d of column kk·G + g holds q[:, kk, g, d]."""
    B, Kh, G, D = q.shape
    eye = jnp.eye(Kh, dtype=q.dtype)
    return (q.transpose(0, 1, 3, 2)[:, :, :, None, :]
            * eye[None, :, None, :, None]).reshape(B, Kh * D, Kh * G)


def own_blocks(out: jax.Array, Kh: int, G: int, D: int) -> jax.Array:
    """(B, Kh·G, Kh·D) -> (B, Kh, G, D): each head's own KV head's block."""
    B = out.shape[0]
    diag = jnp.diagonal(out.reshape(B, Kh, G, Kh, D), axis1=1, axis2=3)
    return diag.transpose(0, 3, 1, 2)


def attention_decode(p: Dict, x: jax.Array, cache: Dict, layer: jax.Array,
                     cfg: ModelConfig, cur_index: jax.Array
                     ) -> Tuple[jax.Array, Dict]:
    """x: (B, 1, M); cache: the layer-stacked K and V ``(L, B, S, Kh·D)``;
    layer: this layer's index; cur_index: (B,) current write position
    (tokens so far). Writes the new K/V row into the stack and attends
    over this layer's slab where it lies.

    Sliding-window archs store a ring buffer of ``window`` positions.
    """
    B = x.shape[0]
    H, Kh, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // Kh
    S = cache["k"].shape[2]
    q, k_new, v_new = qkv_proj(p, x, cfg, cur_index[:, None])
    slot = cur_index % S if cfg.window is not None else cur_index
    cache = {"k": write_row(cache["k"], layer, slot,
                            k_new[:, 0].reshape(B, Kh * D)),
             "v": write_row(cache["v"], layer, slot,
                            v_new[:, 0].reshape(B, Kh * D))}
    k, v = cache["k"][layer], cache["v"][layer]            # (B, S, Kh·D)

    kv_pos = jnp.arange(S)[None, :]                        # (1,S) slot index
    if cfg.window is not None:
        # slot s holds token (cur - ((slot - s) mod S)) — valid if within window
        age = (slot[:, None] - kv_pos) % S
        valid = (age < jnp.minimum(cur_index[:, None] + 1, S))
    else:
        valid = kv_pos <= cur_index[:, None]

    # Both contractions read the slab as it lies, (S, Kh·D) with Kh·D
    # minor, as one matmul per sequence over all heads: the scores
    # against a block-diagonal q, w·V into every head's columns, of
    # which each head keeps its own block. A (B, S, Kh, D) view of the
    # slab would make XLA relayout it. Products keep the operands'
    # precision: bf16 q and K into float32 sums, float32 w (HIGHEST)
    # against V.
    q_exp = block_diagonal(q.reshape(B, Kh, G, D))          # (B, Kh·D, H)
    s = jnp.einsum("bsf,bfh->bsh", k, q_exp,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    s = jnp.where(valid[:, :, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=1)                            # (B, S, H)
    out = own_blocks(jnp.einsum("bsh,bsf->bhf", w, v.astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST),
                     Kh, G, D)
    out = out.reshape(B, 1, H * D).astype(x.dtype)
    y = jnp.einsum("bsh,hm->bsm", out, p["wo"])
    return y, cache
