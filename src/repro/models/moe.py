"""Mixture-of-Experts with sort-based dropless dispatch.

The router scores every expert of the layer (softmax, then greedy
top-k; the gates are renormalized only where ``cfg.norm_topk_prob``
says so, then scaled by ``cfg.routed_scaling``). A layer holds the
weights of ``cfg.experts_held`` consecutive experts from
``cfg.expert_offset`` on: all of them on one chip, or this chip's share
where ``cfg.expert_parallel`` chips divide the layer. The assignments to
held experts are sorted by expert into one buffer of
T · min(top_k, held) rows, which holds every one of them, and each
expert's rows go through its SwiGLU as one group of a ragged matmul: no
token is dropped, and the FLOPs are those of the assignments. What
experts held elsewhere would add is left to their chips.

Shared experts are fused into one dense swiglu of width shared·moe_d_ff.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import SpecTree, param, swiglu


def _experts(key: jax.Array, first: int, n: int, shape: Tuple[int, int],
             axes, specs: SpecTree, name: str) -> jax.Array:
    """Experts ``first .. first + n - 1`` of one weight, stacked: expert e
    is drawn from ``fold_in(key, e)`` at fan-in scale, so its weights do
    not depend on how many experts are held."""
    specs.record(name, axes)

    def one(e):
        w = jax.random.normal(jax.random.fold_in(key, e), shape, jnp.float32)
        return (w * shape[0] ** -0.5).astype(jnp.bfloat16)

    return jax.vmap(one)(jnp.arange(first, first + n))


def init_moe(key: jax.Array, cfg: ModelConfig, specs: SpecTree) -> Dict:
    sub = specs.sub("moe")
    ks = jax.random.split(key, 8)
    M, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    first, n = cfg.expert_offset, cfg.experts_held
    p = {
        "router": param(ks[0], (M, E), ("embed", None), sub, "router",
                        scale=M ** -0.5, dtype=jnp.float32),
        "wi": _experts(ks[1], first, n, (M, F),
                       ("experts", "embed", "moe_ff"), sub, "wi"),
        "wg": _experts(ks[2], first, n, (M, F),
                       ("experts", "embed", "moe_ff"), sub, "wg"),
        "wo": _experts(ks[3], first, n, (F, M),
                       ("experts", "moe_ff", "embed"), sub, "wo"),
    }
    if cfg.num_shared_experts:
        Fs = cfg.num_shared_experts * F
        p["shared_wi"] = param(ks[4], (M, Fs), ("embed", "ffn"), sub, "shared_wi")
        p["shared_wg"] = param(ks[5], (M, Fs), ("embed", "ffn"), sub, "shared_wg")
        p["shared_wo"] = param(ks[6], (Fs, M), ("ffn", "embed"), sub, "shared_wo")
    return p


def _dispatch_core(xt: jax.Array, p: Dict, cfg: ModelConfig
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless dispatch of ``xt`` (T, M) to the ``E_loc`` held experts,
    global ids ``cfg.expert_offset ..`` (EP slice). Returns (y (T, M) f32
    partial, aux, held (T, E_loc) int32: how many of each token's top-k
    went to each held expert, 0 or 1)."""
    T, M = xt.shape
    E, K = cfg.num_experts, cfg.top_k
    E_loc = cfg.experts_held

    logits = jnp.einsum("tm,me->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)
    gate, expert_idx = jax.lax.top_k(probs, K)                    # (T, K)
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    gate = gate * cfg.routed_scaling

    # ---- load-balancing aux loss (Switch-style, over global experts) ----
    me = probs.mean(axis=0)                                       # (E,)
    ce = jnp.zeros(E).at[expert_idx.reshape(-1)].add(1.0) / (T * K)
    aux = cfg.router_aux_weight * E * jnp.sum(me * ce)

    # ---- sort the held assignments by expert: one row each ----
    local_e = expert_idx.reshape(-1) - cfg.expert_offset          # (T*K,)
    in_slice = (local_e >= 0) & (local_e < E_loc)
    flat_e = jnp.where(in_slice, local_e, E_loc)                  # E_loc = out
    rows = T * min(K, E_loc)              # a token sends ≤ min(K, E_loc) here
    order = jnp.argsort(flat_e, stable=True)[:rows]
    held = in_slice[order]
    sizes = jnp.zeros(E_loc + 1, jnp.int32).at[flat_e].add(1)[:E_loc]
    token_of = order // K                                         # (rows,)

    grouped = xt[token_of]                                        # (rows, M)
    h = jax.lax.ragged_dot(grouped, p["wi"], sizes)
    g = jax.lax.ragged_dot(grouped, p["wg"], sizes)
    yg = jax.lax.ragged_dot(h * jax.nn.silu(g), p["wo"], sizes)   # (rows, M)

    w = jnp.where(held, gate.reshape(-1)[order], 0.0)
    y = jnp.zeros((T, M), jnp.float32).at[token_of].add(
        yg.astype(jnp.float32) * w[:, None])
    counts = jax.nn.one_hot(jnp.where(in_slice, local_e, -1), E_loc,
                            dtype=jnp.int32).reshape(T, K, E_loc).sum(1)
    return y, aux, counts


def moe_apply(p: Dict, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, M) → (out, aux_loss, held (B, S, experts held): each
    token's assignments to each held expert)."""
    B, S, M = x.shape
    xt = x.reshape(B * S, M)
    y, aux, held = _dispatch_core(xt, p, cfg)
    if cfg.num_shared_experts:
        y = y + swiglu(xt, p["shared_wi"], p["shared_wg"],
                       p["shared_wo"]).astype(jnp.float32)
    return (y.reshape(B, S, M).astype(x.dtype), aux,
            held.reshape(B, S, cfg.experts_held))

