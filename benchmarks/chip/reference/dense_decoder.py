"""Plain float32 reference of a dense pre-norm decoder.

The architecture of the benchmark's configuration as published: token
embedding; per layer RMSNorm, grouped-query attention with rotary
positions (rotate-half form) and optional Q/K/V bias, a residual add,
RMSNorm, a SwiGLU feed-forward and a residual add; a final RMSNorm and an
unembedding. Written in straightforward ``jax.numpy``: float32
activations, ``highest`` matmul precision, a full causal softmax, no
cache, no kernels. It imports nothing of the system under test.

Weights are drawn from the seed by the random-initialisation recipe the
configuration file states under ``init`` (key split order, scales,
storage dtype), so the reference holds the same numbers as the served
model without taking them from it.

``quant="int8"`` is the control: every projection and the unembedding
take their operands rounded to int8 (symmetric, per-row activation and
per-column weight scales), the precision step below the configurations'
bfloat16. The correctness limits are set between what the program reads
against the float32 reference and what this control reads.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class Dims:
    """The sizes the reference reads from a configuration file."""

    def __init__(self, cfg: Dict) -> None:
        self.layers = cfg["num_hidden_layers"]
        self.d = cfg["hidden_size"]
        self.ff = cfg["intermediate_size"]
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.head_dim = cfg["head_dim"]
        self.vocab = cfg["vocab_size"]
        self.padded_vocab = -(-self.vocab // 128) * 128
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.qkv_bias = cfg["qkv_bias"]
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        self.embed_std = cfg["init"]["embed_std"]


def init_weights(cfg: Dict, key: jax.Array) -> Dict:
    """Weights drawn from the key of the seed, in the configuration's storage dtype, stacked on a
    leading layer axis. The recipe (``cfg["init"]``): the seed's key is
    split into layers + 3 keys; layer keys split in 4 (attention, -, -,
    feed-forward), the attention key in 8 (q, k, v, o, ...), the
    feed-forward key in 3 (wi, wg, wo); the last two keys draw the
    embedding and the unembedding. A matrix is N(0, 1) times
    fan_in^-0.5, drawn in float32 and rounded to the storage dtype;
    biases are zero and norm weights one."""
    n = Dims(cfg)
    M, H, K, D, F = n.d, n.heads, n.kv_heads, n.head_dim, n.ff

    def mat(key, shape, scale=None):
        scale = shape[0] ** -0.5 if scale is None else scale
        return (jax.random.normal(key, shape, F32) * scale).astype(n.dtype)

    def layer(key):
        ks = jax.random.split(key, 4)
        ka = jax.random.split(ks[0], 8)
        kf = jax.random.split(ks[3], 3)
        w = {"wq": mat(ka[0], (M, H * D)), "wk": mat(ka[1], (M, K * D)),
             "wv": mat(ka[2], (M, K * D)), "wo": mat(ka[3], (H * D, M)),
             "wi": mat(kf[0], (M, F)), "wg": mat(kf[1], (M, F)),
             "wf": mat(kf[2], (F, M)),
             "norm_attn": jnp.ones((M,), n.dtype),
             "norm_ffn": jnp.ones((M,), n.dtype)}
        if n.qkv_bias:
            w.update(bq=jnp.zeros((H * D,), n.dtype),
                     bk=jnp.zeros((K * D,), n.dtype),
                     bv=jnp.zeros((K * D,), n.dtype))
        return w

    keys = jax.random.split(key, n.layers + 3)
    return {"layers": jax.vmap(layer)(keys[: n.layers]),
            "embed": mat(keys[-2], (n.padded_vocab, M), n.embed_std),
            "final_norm": jnp.ones((M,), n.dtype),
            "unembed": mat(keys[-1], (M, n.padded_vocab))}


def _fake_int8(x: jax.Array, axis: int) -> jax.Array:
    """x rounded to symmetric int8 along ``axis`` and scaled back."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def matmul(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    """x (..., m) @ w (m, n) in float32, or, for the control, with both
    operands rounded to int8 (per-row activation and per-column weight
    scales) as an int8 x int8 -> int32 matmul would take them."""
    x = x.astype(F32)
    w = w.astype(F32)
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum("...m,mn->...n", x, w, precision=HIGHEST)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (B, S, heads, D), rotate-half form: the first and second halves
    of each head are the two coordinates of every rotated pair."""
    D = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = positions[..., None].astype(F32) * freqs          # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(w: Dict, h: jax.Array, n: Dims, quant: Optional[str]):
    B, S, _ = h.shape
    H, K, D = n.heads, n.kv_heads, n.head_dim
    q = matmul(h, w["wq"], quant)
    k = matmul(h, w["wk"], quant)
    v = matmul(h, w["wv"], quant)
    if n.qkv_bias:
        q, k, v = (q + w["bq"].astype(F32), k + w["bk"].astype(F32),
                   v + w["bv"].astype(F32))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q = rope(q.reshape(B, S, H, D), pos, n.theta)
    k = rope(k.reshape(B, S, K, D), pos, n.theta)
    v = v.reshape(B, S, K, D)
    # query head h reads kv head h // (H // K)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * D ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    return matmul(o.reshape(B, S, H * D), w["wo"], quant)


def hidden(weights: Dict, tokens: jax.Array, cfg: Dict,
           quant: Optional[str] = None) -> jax.Array:
    """Final-normed hidden states (B, S, d) of token ids (B, S)."""
    n = Dims(cfg)
    x = weights["embed"][tokens].astype(F32)

    def layer(x, w):
        x = x + attention(w, rms_norm(x, w["norm_attn"], n.eps), n, quant)
        h = rms_norm(x, w["norm_ffn"], n.eps)
        g = matmul(h, w["wg"], quant)
        x = x + matmul(matmul(h, w["wi"], quant) * jax.nn.silu(g),
                       w["wf"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    return rms_norm(x, weights["final_norm"], n.eps)


def logits(weights: Dict, h: jax.Array, cfg: Dict,
           quant: Optional[str] = None) -> jax.Array:
    """Logits over the real vocabulary of hidden states h (..., d)."""
    return matmul(h, weights["unembed"], quant)[..., : cfg["vocab_size"]]

