"""jit wrapper for the flash attention kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                              "kv_block", "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True,
                       window: Optional[int] = None, q_block: int = 256,
                       kv_block: int = 256, interpret: bool = False):
    return flash_attention(q, k, v, causal=causal, window=window,
                           q_block=q_block, kv_block=kv_block,
                           interpret=interpret)
