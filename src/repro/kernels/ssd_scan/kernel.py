"""Pallas TPU SSD (Mamba-2) chunk scan.

Grid (B, H, nC): the chunk axis is minor-most, so the per-(b,h) SSM state
lives in VMEM scratch across the sequential chunk sweep. Each chunk does
the SSD block decomposition entirely on the MXU:

  intra:  Y += ((C·Bᵀ) ⊙ L ⊙ dtⱼ) · X          (K×K quadratic, K small)
  inter:  Y += exp(dA_cs) ⊙ (C · h_prev)
  state:  h = exp(dA_sum)·h_prev + (dt·decay_out·B)ᵀ · X

The (K,N) B/C blocks are shared across heads (n_groups=1), re-read per
head — the BlockSpec index map drops the head coordinate for them. The
chunk's dt arrives as a lane row; the cumulative sum is a matmul with a
triangular mask, so the body needs no transpose or scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_to_col(row: jax.Array, eye: jax.Array) -> jax.Array:
    """(1, K) → (K, 1) without a transpose: mask the diagonal, sum lanes."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, h_s, *, chunk: int):
    h, ci = pl.program_id(1), pl.program_id(2)
    K = chunk
    hi = jax.lax.Precision.HIGHEST

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())), precision=hi,
                                   preferred_element_type=jnp.float32)

    @pl.when(ci == 0)
    def _():
        h_s[...] = jnp.zeros_like(h_s)

    x = x_ref[0, 0].astype(jnp.float32)              # (K, P)
    Bm = b_ref[0].astype(jnp.float32)                # (K, N)
    Cm = c_ref[0].astype(jnp.float32)                # (K, N)
    dt = dt_ref[0, 0, pl.ds(ci, 1), :].astype(jnp.float32)   # (1, K) row
    A = a_ref[h]                                     # scalar (this head)

    ii = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    eye = ii == jj
    dA = dt * A                                      # (1, K)
    dA_cs = dot(dA, jnp.where(ii <= jj, 1.0, 0.0), ((1,), (0,)))  # cumsum
    cs_col = _row_to_col(dA_cs, eye)                 # (K, 1)
    total = jnp.sum(dA, axis=1, keepdims=True)       # (1, 1) = dA_cs[-1]
    # intra-chunk
    Lmat = jnp.where(ii >= jj, jnp.exp(cs_col - dA_cs), 0.0)   # (K, K)
    qk = dot(Cm, Bm, ((1,), (1,)))                   # (K, K)
    y = dot(qk * Lmat * dt, x, ((1,), (0,)))         # (K, P)
    # inter-chunk (inbound state)
    h_prev = h_s[...]                                # (N, P)
    y += jnp.exp(cs_col) * dot(Cm, h_prev, ((1,), (0,)))
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update
    w = _row_to_col(dt * jnp.exp(total - dA_cs), eye)   # (K, 1)
    h_s[...] = h_prev * jnp.exp(total) + dot(Bm * w, x, ((0,), (0,)))


def ssd_scan(x: jax.Array, Bm: jax.Array, Cm: jax.Array, dt: jax.Array,
             A: jax.Array, *, chunk: int = 64,
             interpret: bool = False) -> jax.Array:
    """x: (B, L, H, P); Bm/Cm: (B, L, N); dt: (B, L, H); A: (H,)."""
    Bb, L, H, P = x.shape
    N = Bm.shape[-1]
    if L % chunk:
        raise ValueError("L must be a multiple of chunk")
    nC = L // chunk

    # heads ahead of the sequence so each block ends in (chunk, P); dt is
    # kept whole per (b, h) as (nC, chunk) rows and the chunk's row read
    # in the kernel
    xt = jnp.swapaxes(x, 1, 2)                       # (B, H, L, P)
    dtt = jnp.swapaxes(dt, 1, 2).reshape(Bb, H, nC, chunk)
    kernel = functools.partial(_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(Bb, H, nC),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, nC, chunk), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # A: one scalar per head
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, Bm, Cm, dtt, A.astype(jnp.float32))
    return jnp.swapaxes(y, 1, 2)
