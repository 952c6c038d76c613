"""jit wrapper for the SSD chunk-scan kernel."""

from __future__ import annotations

import functools

import jax

from .kernel import ssd_scan


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_op(x, Bm, Cm, dt, A, *, chunk: int = 64,
                interpret: bool = False):
    return ssd_scan(x, Bm, Cm, dt, A, chunk=chunk, interpret=interpret)
