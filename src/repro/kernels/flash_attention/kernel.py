"""Pallas TPU flash attention (forward): online softmax over KV blocks.

Grid (B, H, nq, nkv) — TPU iterates the minor-most axis sequentially, so
the (m, l, acc) scratch persists across the nkv sweep for one (b, h, qi)
output block. The wrapper puts heads ahead of the sequence, so every
block's last two dims are (block, D), the shape the TPU tiles. Causal
blocks entirely in the future are SKIPPED with pl.when (no MXU work),
recovering the ~2× triangular saving the pure-jnp reference wastes;
sliding-window additionally skips blocks left of the window. BlockSpec
tiling keeps VMEM at (q_block·D + 2·kv_block·D + acc).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s,
            *, causal: bool, window: Optional[int], q_block: int,
            kv_block: int, num_kv: int, sq: int, skv: int, scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_pos = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0) + (skv - sq)
    kv_pos = ki * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)                # (qb, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (kb, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = kv_pos < skv
        if causal:
            mask &= kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_s[...], l_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    if causal or window is not None:
        # block-level skip: entire block in the future / left of window
        first_q = qi * q_block + (skv - sq)
        last_q = first_q + q_block - 1
        first_kv, last_kv = ki * kv_block, ki * kv_block + kv_block - 1
        live = jnp.bool_(True)
        if causal:
            live &= first_kv <= last_q
        if window is not None:
            live &= last_kv > first_q - window
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(ki == num_kv - 1)
    def _():
        out = acc_s[...] / jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_block: int = 256, kv_block: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Skv, Kh, D). Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Skv, Kh, _ = k.shape
    G = H // Kh
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    if Sq % q_block or Skv % kv_block:
        raise ValueError("seq lens must divide block sizes")
    nq, nkv = Sq // q_block, Skv // kv_block

    kernel = functools.partial(
        _kernel, causal=causal, window=window, q_block=q_block,
        kv_block=kv_block, num_kv=nkv, sq=Sq, skv=Skv, scale=D ** -0.5)

    q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))   # (B, heads, S, D)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kv_block, D),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, D),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out, 1, 2)
