"""Plain float32 reference of DeepSeek-V2's decoder: latent attention,
a leading dense layer, then routed and shared experts.

The architecture as published (DeepSeek-V2-Lite's ``config.json``):
token embedding; per layer RMSNorm, multi-head latent attention, a
residual add, RMSNorm, a feed-forward and a residual add; a final
RMSNorm and an unembedding. The first ``first_k_dense_replace`` layers'
feed-forward is a SwiGLU of ``intermediate_size``; every later layer's
is a softmax router over all ``router_outputs`` experts, greedy top-k
gates (renormalized only where ``norm_topk_prob`` says so, times
``routed_scaling_factor``), the routed experts' SwiGLUs weighted by their
gates, plus the shared experts' SwiGLU.

Attention is written out plainly: keys and values are expanded per head
from the latent (no absorption), the rope part is DeepSeek's interleaved
form (the pairs x[2i], x[2i+1]) with YaRN frequencies from
``rope_scaling``, and the softmax scale is (qk_nope + qk_rope)^-0.5 times
YaRN's mscale(factor, mscale_all_dim)², with a full causal softmax.

Experts: the configuration holds ``n_routed_experts`` of them, the share
of rank ``deployment.rank`` of an expert-parallel deployment (experts
rank·n .. rank·n + n - 1 of the router's outputs). They are computed for
every token, densely, and masked by their gates; what experts held
elsewhere would add is left out, as on the chip it stands for.

Float32 activations, ``highest`` matmul precision; weights are kept in
the storage dtype and upcast a layer at a time, so the model fits beside
the check. Weights are drawn from the seed by the recipe the
configuration states under ``init``. It imports nothing of the system
under test. ``quant="int8"`` is the control, as in ``dense_decoder``:
every projection, the router and the unembedding take int8-rounded
operands.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .dense_decoder import F32, HIGHEST, matmul, rms_norm


class Dims:
    """The sizes the reference reads from a configuration file."""

    def __init__(self, cfg: Dict) -> None:
        self.layers = cfg["num_hidden_layers"]
        self.dense_layers = cfg["first_k_dense_replace"]
        self.d = cfg["hidden_size"]
        self.ff = cfg["intermediate_size"]
        self.expert_ff = cfg["moe_intermediate_size"]
        self.heads = cfg["num_attention_heads"]
        self.rank = cfg["kv_lora_rank"]
        self.nope = cfg["qk_nope_head_dim"]
        self.rope = cfg["qk_rope_head_dim"]
        self.v = cfg["v_head_dim"]
        self.vocab = cfg["vocab_size"]
        self.padded_vocab = -(-self.vocab // 128) * 128
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.yarn = cfg["rope_scaling"]
        self.routed = cfg["router_outputs"]
        self.held = cfg["n_routed_experts"]
        self.first_held = cfg["deployment"]["rank"] * self.held
        self.top_k = cfg["num_experts_per_tok"]
        self.shared = cfg["n_shared_experts"]
        self.norm_topk = cfg["norm_topk_prob"]
        self.routed_scale = cfg["routed_scaling_factor"]
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        self.embed_std = cfg["init"]["embed_std"]


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(n: Dims) -> jax.Array:
    """Pair i's frequency: theta^(-2i/d), divided by the factor where the
    pair turns fewer than beta_slow times over the original context, kept
    where it turns more than beta_fast times, a linear ramp between."""
    d, y = n.rope, n.yarn
    base = 1.0 / (n.theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

    def dim_of(turns):
        return (d * math.log(y["original_max_position_embeddings"]
                             / (turns * 2 * math.pi))
                / (2 * math.log(n.theta)))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low)
                    / (high - low if high != low else 0.001), 0.0, 1.0)
    return base / y["factor"] * ramp + base * (1.0 - ramp)


def softmax_scale(n: Dims) -> float:
    y = n.yarn
    return ((n.nope + n.rope) ** -0.5
            * _mscale(y["factor"], y["mscale_all_dim"]) ** 2)


def init_weights(cfg: Dict, key: jax.Array) -> Dict:
    """Weights drawn from the key of the seed, in the configuration's
    storage dtype. The recipe (``cfg["init"]``): the seed's key is split
    into layers + 3 keys, layer key i drawing layer i; a layer key splits
    in 4 (attention, -, experts, dense feed-forward); the attention key
    in 6 (wq, wkv_a, -, wk_b, wv_b, wo); the dense key in 3 (wi, wg,
    wo); the experts key in 8 (router, wi, wg, wo, shared wi, shared wg,
    shared wo, -), expert e's weights drawn from ``fold_in`` of the
    weight's key with e. A matrix is N(0, 1) times fan_in^-0.5 drawn in
    float32 and rounded to the storage dtype (the router stays float32);
    norm weights are one. The last two keys draw the embedding and the
    unembedding."""
    n = Dims(cfg)
    M, H, R = n.d, n.heads, n.rank

    def mat(key, shape, scale=None, dtype=None):
        scale = shape[0] ** -0.5 if scale is None else scale
        return (jax.random.normal(key, shape, F32) * scale).astype(
            dtype or n.dtype)

    def ones(k):
        return jnp.ones((k,), n.dtype)

    def attention(key):
        ka = jax.random.split(key, 6)
        return {"wq": mat(ka[0], (M, H * (n.nope + n.rope))),
                "wkv_a": mat(ka[1], (M, R + n.rope)),
                "kv_norm": ones(R),
                "wk_b": mat(ka[3], (R, H * n.nope)),
                "wv_b": mat(ka[4], (R, H * n.v)),
                "wo": mat(ka[5], (H * n.v, M)),
                "norm_attn": ones(M), "norm_ffn": ones(M)}

    def dense_layer(key):
        ks = jax.random.split(key, 4)
        kf = jax.random.split(ks[3], 3)
        return {**attention(ks[0]), "wi": mat(kf[0], (M, n.ff)),
                "wg": mat(kf[1], (M, n.ff)), "wf": mat(kf[2], (n.ff, M))}

    def moe_layer(key):
        ks = jax.random.split(key, 4)
        km = jax.random.split(ks[2], 8)
        held = jnp.arange(n.first_held, n.first_held + n.held)
        F, Fs = n.expert_ff, n.shared * n.expert_ff

        def experts(k, shape):
            return jax.vmap(lambda e: mat(jax.random.fold_in(k, e),
                                          shape))(held)

        return {**attention(ks[0]),
                "router": mat(km[0], (M, n.routed), dtype=F32),
                "wi": experts(km[1], (M, F)), "wg": experts(km[2], (M, F)),
                "wf": experts(km[3], (F, M)),
                "shared_wi": mat(km[4], (M, Fs)),
                "shared_wg": mat(km[5], (M, Fs)),
                "shared_wf": mat(km[6], (Fs, M))}

    keys = jax.random.split(key, n.layers + 3)
    nd = n.dense_layers
    return {"dense": jax.vmap(dense_layer)(keys[:nd]),
            "layers": jax.vmap(moe_layer)(keys[nd: n.layers]),
            "embed": mat(keys[-2], (n.padded_vocab, M), n.embed_std),
            "final_norm": ones(M),
            "unembed": mat(keys[-1], (M, n.padded_vocab))}


def rope(x: jax.Array, n: Dims) -> jax.Array:
    """x (B, S, heads, D): pair i is (x[2i], x[2i+1]), turned by position
    times YaRN's frequency i; cos and sin times mscale over
    mscale_all_dim."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=F32)[:, None] * yarn_inv_freq(n)   # (S, D/2)
    y = n.yarn
    m = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"],
                                                     y["mscale_all_dim"])
    cos, sin = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def attention(w: Dict, h: jax.Array, n: Dims, quant: Optional[str]):
    B, S, _ = h.shape
    H, R = n.heads, n.rank
    q = matmul(h, w["wq"], quant).reshape(B, S, H, n.nope + n.rope)
    q_nope, q_pe = q[..., : n.nope], rope(q[..., n.nope:], n)
    kv = matmul(h, w["wkv_a"], quant)
    latent = rms_norm(kv[..., :R], w["kv_norm"], n.eps)
    k_pe = rope(kv[..., None, R:], n)                         # (B, S, 1, dr)
    k_nope = matmul(latent, w["wk_b"], quant).reshape(B, S, H, n.nope)
    v = matmul(latent, w["wv_b"], quant).reshape(B, S, H, n.v)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, precision=HIGHEST)
         + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0],
                      precision=HIGHEST)) * softmax_scale(n)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    return matmul(o.reshape(B, S, H * n.v), w["wo"], quant)


def swiglu(h, wi, wg, wf, quant):
    return matmul(matmul(h, wi, quant) * jax.nn.silu(matmul(h, wg, quant)),
                  wf, quant)


def experts(w: Dict, h: jax.Array, n: Dims, quant: Optional[str]):
    """The held experts' gated outputs plus the shared experts'."""
    probs = jax.nn.softmax(matmul(h, w["router"], quant), axis=-1)
    gate, idx = jax.lax.top_k(probs, n.top_k)                 # (B, S, k)
    if n.norm_topk:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * n.routed_scale
    held = n.first_held + jnp.arange(n.held)
    g = jnp.sum(gate[..., None] * (idx[..., None] == held), -2)  # (B,S,E)
    out = jax.vmap(lambda wi, wg, wf: swiglu(h, wi, wg, wf, quant))(
        w["wi"], w["wg"], w["wf"])                             # (E,B,S,M)
    return (jnp.einsum("ebsm,bse->bsm", out, g, precision=HIGHEST)
            + swiglu(h, w["shared_wi"], w["shared_wg"], w["shared_wf"],
                     quant))


def hidden(weights: Dict, tokens: jax.Array, cfg: Dict,
           quant: Optional[str] = None) -> jax.Array:
    """Final-normed hidden states (B, S, d) of token ids (B, S)."""
    n = Dims(cfg)
    x = weights["embed"][tokens].astype(F32)

    def dense(x, w):
        x = x + attention(w, rms_norm(x, w["norm_attn"], n.eps), n, quant)
        h = rms_norm(x, w["norm_ffn"], n.eps)
        return x + swiglu(h, w["wi"], w["wg"], w["wf"], quant), None

    def routed(x, w):
        x = x + attention(w, rms_norm(x, w["norm_attn"], n.eps), n, quant)
        return x + experts(w, rms_norm(x, w["norm_ffn"], n.eps), n,
                           quant), None

    x, _ = jax.lax.scan(dense, x, weights["dense"])
    x, _ = jax.lax.scan(routed, x, weights["layers"])
    return rms_norm(x, weights["final_norm"], n.eps)


def logits(weights: Dict, h: jax.Array, cfg: Dict,
           quant: Optional[str] = None) -> jax.Array:
    """Logits over the real vocabulary of hidden states h (..., d)."""
    return matmul(h, weights["unembed"], quant)[..., : cfg["vocab_size"]]
