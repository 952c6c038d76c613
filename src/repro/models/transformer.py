"""Decoder stack: homogeneous blocks scanned over the layer axis.

Block = pre-norm mixer (attn | ssm | hybrid-parallel) + pre-norm FFN
(dense | MoE). Parameters of all layers are stacked on a leading "layers"
axis so the stack is one `lax.scan` — small HLO, fast compiles, and remat
policy applies per-layer. An MoE model's ``first_k_dense`` leading
layers have a dense FFN of width ``d_ff``: they are stacked apart
(``dense_blocks``) and run before the scan over the MoE blocks. Layer
key i draws layer i, whatever its kind, and every per-layer cache is one
stack over all layers.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import SpecTree, apply_mlp, init_mlp, init_norm, rms_norm

PyTree = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _ffn(cfg: ModelConfig, dense: bool) -> Optional[str]:
    """The FFN of a layer: "moe", "mlp" or None. ``dense`` marks one of
    an MoE model's leading dense layers."""
    if cfg.uses_moe and not dense:
        return "moe"
    return "mlp" if cfg.d_ff else None


def init_block(key: jax.Array, cfg: ModelConfig, specs: SpecTree,
               dense: bool = False) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict = {"norm_mixer": init_norm(cfg.d_model, specs, "norm_mixer"),
               "norm_ffn": init_norm(cfg.d_model, specs, "norm_ffn")}
    if cfg.uses_attention:
        if cfg.attention == "mla":
            p["mla"] = mla_mod.init_mla(ks[0], cfg, specs)
        else:
            p["attn"] = attn_mod.init_attention(ks[0], cfg, specs)
    if cfg.uses_ssm:
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg, specs)
    ffn = _ffn(cfg, dense)
    if ffn == "moe":
        p["moe"] = moe_mod.init_moe(ks[2], cfg, specs)
    elif ffn == "mlp":
        p["mlp"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff, specs)
    return p


def init_stack(key: jax.Array, cfg: ModelConfig) -> Tuple[Dict, Dict]:
    """Returns (params, logical_specs) with block params stacked on axis 0."""
    def stacked(layer_keys, dense):
        """Blocks of ``layer_keys`` stacked on a leading "layers" axis,
        and their specs (recorded while their shapes are traced)."""
        spec_obj = SpecTree()
        jax.eval_shape(lambda k: init_block(k, cfg, spec_obj, dense),
                       layer_keys[0])
        specs = jax.tree.map(lambda axes: ("layers",) + tuple(axes),
                             spec_obj.specs,
                             is_leaf=lambda x: isinstance(x, tuple))
        return jax.vmap(lambda k: init_block(k, cfg, SpecTree(), dense))(
            layer_keys), specs

    nd = cfg.first_k_dense
    keys = jax.random.split(key, cfg.num_layers + 3)
    blocks, block_axis_specs = stacked(keys[nd: cfg.num_layers], False)

    ek, uk = keys[-2], keys[-1]
    from .layers import param  # local import to avoid cycle noise
    top = SpecTree()
    params = {
        "embed": param(ek, (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                       top, "embed", scale=1.0),
        "blocks": blocks,
        "final_norm": init_norm(cfg.d_model, top, "final_norm"),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = param(uk, (cfg.d_model, cfg.padded_vocab),
                                  ("embed", "vocab"), top, "unembed")
    spec_tree = dict(top.specs)
    spec_tree["blocks"] = block_axis_specs
    if nd:
        params["dense_blocks"], spec_tree["dense_blocks"] = stacked(
            keys[:nd], True)
    return params, spec_tree


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def block_forward(p: Dict, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array, collect_cache: bool = False,
                  dense: bool = False):
    """Returns (x_out, aux_loss, cache_piece-or-None)."""
    aux = jnp.zeros((), jnp.float32)
    piece: Dict = {}
    h = rms_norm(x, p["norm_mixer"], cfg.norm_eps)
    mixed = jnp.zeros_like(x)
    if cfg.uses_attention:
        if cfg.attention == "mla":
            r = mla_mod.mla_train(p["mla"], h, cfg, positions,
                                  return_kv=collect_cache)
            if collect_cache:
                r, piece["mla"] = r
            mixed = mixed + r
        else:
            r = attn_mod.attention_train(p["attn"], h, cfg, positions,
                                         return_kv=collect_cache)
            if collect_cache:
                r, piece["attn"] = r
            mixed = mixed + r
    if cfg.uses_ssm:
        s = ssm_mod.ssm_train(p["ssm"], h, cfg, positions,
                              return_state=collect_cache)
        if collect_cache:
            s, piece["ssm"] = s
        mixed = 0.5 * (mixed + s) if cfg.mixer == "hybrid" else mixed + s
    x = x + mixed
    h = rms_norm(x, p["norm_ffn"], cfg.norm_eps)
    ffn = _ffn(cfg, dense)
    if ffn == "moe":
        y, aux, held = moe_mod.moe_apply(p["moe"], h, cfg)
        piece["moe"] = _routing_counts(held.sum(1))
    elif ffn == "mlp":
        y = apply_mlp(p["mlp"], h)
    else:
        y = jnp.zeros_like(h)
    if cfg.uses_moe and dense:
        piece["moe"] = _routing_counts(
            jnp.zeros((x.shape[0], cfg.experts_held), jnp.int32))
    return x + y, aux, (piece if collect_cache else None)


def _routing_counts(assign: jax.Array) -> Dict:
    """An MoE layer's cache piece: ``assign`` (B, experts held), each
    sequence's assignments to each held expert so far, and ``load_max``,
    the most assignments one held expert took in one decode step so far
    (the same in every sequence's row; none yet)."""
    return {"assign": assign.astype(jnp.int32),
            "load_max": jnp.zeros_like(assign, jnp.int32)}


def forward(params: Dict, tokens_or_embeds: jax.Array, cfg: ModelConfig,
            *, remat: str = "none", collect_cache: bool = False,
            positions: Optional[jax.Array] = None):
    """tokens (B,S) int32 or precomputed embeddings (B,S,M) for stubbed
    modality frontends. Returns (logits, aux_loss[, cache])."""
    if tokens_or_embeds.ndim == 2:
        x = params["embed"][tokens_or_embeds]
    else:
        x = tokens_or_embeds.astype(params["embed"].dtype)
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def layers(dense):
        def scan_fn(carry, layer_params):
            x, aux = carry
            x, a, piece = block_forward(layer_params, x, cfg, positions,
                                        collect_cache=collect_cache,
                                        dense=dense)
            return (x, aux + a), piece
        return jax.checkpoint(scan_fn) if remat == "full" else scan_fn

    carry = (x, jnp.zeros((), jnp.float32))
    if cfg.first_k_dense:
        carry, lead = jax.lax.scan(layers(True), carry,
                                   params["dense_blocks"])
    (x, aux), cache = jax.lax.scan(layers(False), carry, params["blocks"])
    if collect_cache and cfg.first_k_dense:
        cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), lead,
                             cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = jnp.einsum("bsm,mv->bsv", x, unembed)
    if collect_cache:
        return logits, aux, cache
    return logits, aux


def loss_fn(params: Dict, tokens: jax.Array, targets: jax.Array,
            cfg: ModelConfig, *, remat: str = "none") -> Tuple[jax.Array, Dict]:
    logits, aux = forward(params, tokens, cfg, remat=remat)
    logits = logits.astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:          # mask pad-vocab columns
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    nll = jnp.sum((logz - gold) * mask) / jnp.maximum(mask.sum(), 1.0)
    loss = nll + aux
    return loss, {"nll": nll, "aux": aux}


def prefill(params: Dict, tokens_or_embeds: jax.Array, cfg: ModelConfig,
            *, remat: str = "none") -> Tuple[jax.Array, Dict]:
    """Prefill pass: last-position logits + populated per-layer cache."""
    logits, _, cache = forward(params, tokens_or_embeds, cfg, remat=remat,
                               collect_cache=True)
    return logits[:, -1], cache


# ---------------------------------------------------------------------------
# decode (single token step over the whole stack)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    """Per-layer caches stacked on a leading layer axis."""
    def stack(make):
        one = make()
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape),
            one)

    cache: Dict = {}
    if cfg.uses_attention:
        if cfg.attention == "mla":
            cache["mla"] = stack(lambda: mla_mod.init_mla_cache(cfg, batch, max_len, dtype))
        else:
            cache["attn"] = stack(lambda: attn_mod.init_kv_cache(cfg, batch, max_len, dtype))
    if cfg.uses_ssm:
        cache["ssm"] = stack(lambda: ssm_mod.init_ssm_cache(cfg, batch))
    if cfg.uses_moe:
        cache["moe"] = stack(lambda: _routing_counts(
            jnp.zeros((batch, cfg.experts_held), jnp.int32)))
    return cache


def splice_prefill(cache: Dict, pcache: Dict) -> Dict:
    """``prefill``'s cache written into the front of an ``init_cache``
    decode cache, by kind: attention and MLA rows go to the first
    positions of each layer's slab (a windowed prefill's rows are
    already in ring order), SSM state and MoE counts are taken whole."""
    def rows(full, part):
        return full.at[:, :, :part.shape[2]].set(part.astype(full.dtype))

    def whole(full, part):
        return part.astype(full.dtype)

    return {kind: jax.tree.map(rows if kind in ("attn", "mla") else whole,
                               cache[kind], pcache[kind])
            for kind in cache}


def cache_specs(cfg: ModelConfig) -> Dict:
    """Logical-axis tree mirroring init_cache()'s structure."""
    specs: Dict = {}
    if cfg.uses_attention:
        if cfg.attention == "mla":
            specs["mla"] = mla_mod.mla_cache_specs()
        else:
            specs["attn"] = attn_mod.kv_cache_specs()
    if cfg.uses_ssm:
        specs["ssm"] = ssm_mod.ssm_cache_specs()
    if cfg.uses_moe:
        specs["moe"] = {"assign": ("layers", "batch", None),
                        "load_max": ("layers", "batch", None)}
    return specs


def block_decode(p: Dict, x: jax.Array, cache: Dict, layer: jax.Array,
                 cfg: ModelConfig, cur_index: jax.Array, dense: bool = False
                 ) -> Tuple[jax.Array, Dict]:
    """One layer's decode against the layer-stacked cache. Attention and
    MLA write their new row into the stack at ``layer``; the SSM state
    is the whole state, so the layer's is written back whole; an MoE
    layer adds its step's routing to its counts."""
    cache = dict(cache)
    h = rms_norm(x, p["norm_mixer"], cfg.norm_eps)
    mixed = jnp.zeros_like(x)
    if cfg.uses_attention:
        if cfg.attention == "mla":
            a, cache["mla"] = mla_mod.mla_decode(
                p["mla"], h, cache["mla"], layer, cfg, cur_index)
        else:
            a, cache["attn"] = attn_mod.attention_decode(
                p["attn"], h, cache["attn"], layer, cfg, cur_index)
        mixed = mixed + a
    if cfg.uses_ssm:
        state = jax.tree.map(lambda c: c[layer], cache["ssm"])
        s, state = ssm_mod.ssm_decode(p["ssm"], h, state, cfg, cur_index)
        cache["ssm"] = jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(
                c, n.astype(c.dtype), layer, 0), cache["ssm"], state)
        mixed = 0.5 * (mixed + s) if cfg.mixer == "hybrid" else mixed + s
    x = x + mixed
    h = rms_norm(x, p["norm_ffn"], cfg.norm_eps)
    ffn = _ffn(cfg, dense)
    if ffn == "moe":
        y, _, held = moe_mod.moe_apply(p["moe"], h, cfg)
        held = held[:, 0]                                    # (B, held)
        counts = cache["moe"]
        cache["moe"] = {
            "assign": counts["assign"].at[layer].add(held),
            "load_max": counts["load_max"].at[layer].max(
                held.sum(0)[None, :])}
    elif ffn == "mlp":
        y = apply_mlp(p["mlp"], h)
    else:
        y = jnp.zeros_like(h)
    return x + y, cache


def decode_step(params: Dict, cache: Dict, token_or_embed: jax.Array,
                cur_index: jax.Array, cfg: ModelConfig
                ) -> Tuple[jax.Array, Dict]:
    """One decode step. token (B,) int32 or embed (B, M). cur_index (B,).

    The stacked cache is the layer loop's carry, not its ``xs``/``ys``:
    each layer writes its new rows into it in place and reads its slab
    where it lies, so a step moves the rows it writes and the slabs
    attention reads, never a copy of the cache (donate it to the jitted
    step, or the first write copies it once)."""
    if token_or_embed.ndim == 1:
        x = params["embed"][token_or_embed][:, None, :]      # (B,1,M)
    else:
        x = token_or_embed[:, None, :].astype(params["embed"].dtype)

    def layers(dense):
        def scan_fn(carry, inp):
            x, cache = carry
            layer, layer_params = inp
            return block_decode(layer_params, x, cache, layer, cfg,
                                cur_index, dense), None
        return scan_fn

    nd = cfg.first_k_dense
    carry = (x, cache)
    if nd:
        carry, _ = jax.lax.scan(layers(True), carry,
                                (jnp.arange(nd), params["dense_blocks"]))
    (x, cache), _ = jax.lax.scan(
        layers(False), carry,
        (jnp.arange(nd, cfg.num_layers), params["blocks"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = jnp.einsum("bsm,mv->bsv", x, unembed)[:, 0]
    return logits, cache
