"""Shared building blocks: params-with-logical-axes, norms, RoPE, MLP.

Every parameter leaf is created through ``param()`` which also records a
tuple of *logical axis names*; ``repro.distributed.sharding`` maps those to
mesh axes. Param trees are plain nested dicts (pytrees); specs trees mirror
them exactly.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

PARAM_DTYPE = jnp.bfloat16


class SpecTree:
    """Collects logical-axis specs alongside params during init."""

    def __init__(self) -> None:
        self.specs: Dict = {}

    def sub(self, name: str) -> "SpecTree":
        child = SpecTree()
        self.specs[name] = child.specs
        return child

    def record(self, name: str, axes: Tuple[Optional[str], ...]) -> None:
        self.specs[name] = axes


def param(key: jax.Array, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
          specs: SpecTree, name: str, scale: Optional[float] = None,
          dtype=PARAM_DTYPE) -> jax.Array:
    assert len(shape) == len(axes), f"{name}: shape {shape} vs axes {axes}"
    specs.record(name, axes)
    if scale is None:
        scale = shape[0] ** -0.5 if len(shape) > 1 else 0.0
    if scale == 0.0:
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def ones_param(shape, axes, specs: SpecTree, name: str, dtype=PARAM_DTYPE):
    specs.record(name, axes)
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def rope_freqs(dim: int, theta: float, yarn=None) -> jax.Array:
    """Inverse frequencies of the ``dim / 2`` rotated pairs. With ``yarn``
    (a ``configs.base.Yarn``), pair i's frequency is divided by the
    factor in proportion to a ramp that is 0 for the pairs that turn
    more than ``beta_fast`` times over the original context and 1 for
    those that turn fewer than ``beta_slow`` times (YaRN, as DeepSeek-V2
    computes it)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if yarn is None:
        return inv

    def turns_dim(turns):
        return dim * math.log(yarn.original_max_position
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_dim(yarn.beta_slow)), dim - 1)
    if high == low:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor, 0.1·m·ln(s) + 1 (1 for s ≤ 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: (..., S). Rotate-half
    form: x[i] and x[i + D/2] are pair i. With ``yarn``, YaRN frequencies
    and cos/sin times mscale / mscale_all_dim."""
    dim = x.shape[-1]
    freqs = rope_freqs(dim, theta, yarn)                 # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    if x.ndim == angles.ndim + 1:                        # has head axis
        angles = angles[..., None, :]                    # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array) -> jax.Array:
    h = jnp.einsum("...m,mf->...f", x, wi)
    g = jnp.einsum("...m,mf->...f", x, wg)
    return jnp.einsum("...f,fm->...m", h * jax.nn.silu(g), wo)


def init_mlp(key: jax.Array, d_model: int, d_ff: int, specs: SpecTree) -> Dict:
    sub = specs.sub("mlp")
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": param(k1, (d_model, d_ff), ("embed", "ffn"), sub, "wi"),
        "wg": param(k2, (d_model, d_ff), ("embed", "ffn"), sub, "wg"),
        "wo": param(k3, (d_ff, d_model), ("ffn", "embed"), sub, "wo"),
    }


def apply_mlp(p: Dict, x: jax.Array) -> jax.Array:
    return swiglu(x, p["wi"], p["wg"], p["wo"])


def init_norm(d_model: int, specs: SpecTree, name: str) -> jax.Array:
    return ones_param((d_model,), ("embed",), specs, name)
