"""TPU-kernel embodiment: run-coalescing effect in paged decode attention.

Structural results (exact, hardware-independent): DMA descriptors issued
per decode step with coalescing R=1 (per-page baseline) vs R=4/8, for
contiguity-preserving vs fragmented allocators. Times the compiled kernel
per call; the kernel only compiles for a TPU, so on any other backend
the module fails instead of timing something else.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.ops import (descriptor_stats,
                                               paged_attention)
from repro.kernels.paged_attention.ref import paged_attention_ref

from .common import csv_row


def make_tables(B, Pmax, P, fragmented: bool, rng):
    table = -np.ones((B, Pmax), np.int32)
    cursor = 0
    for b in range(B):
        n = Pmax
        if fragmented:
            table[b, :n] = rng.choice(P, size=n, replace=False)
        else:
            table[b, :n] = np.arange(cursor, cursor + n)
            cursor += n
    return table


def main() -> list:
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(f"paged-attention timing needs a TPU, found "
                           f"{device.platform!r}")
    out = []
    rng = np.random.default_rng(0)
    B, H, Kh, D, T, Pmax = 4, 8, 4, 64, 16, 16
    P = B * Pmax + 8
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(P, T, 2, Kh, D)), jnp.float32)
    lengths = jnp.full((B,), Pmax * T, jnp.int32)
    for frag in (False, True):
        table = make_tables(B, Pmax, P, frag, rng)
        ref = paged_attention_ref(q, kv, jnp.asarray(table), lengths)
        for R in (1, 4, 8):
            stats = descriptor_stats(table, R)
            paged_attention(q, kv, table, lengths,
                            pages_per_block=R).block_until_ready()  # warm-up
            t0 = time.perf_counter()
            o = paged_attention(q, kv, table, lengths, pages_per_block=R)
            o.block_until_ready()
            dt = (time.perf_counter() - t0) * 1e6
            err = float(jnp.abs(o - ref).max())
            name = "frag" if frag else "contig"
            out.append(csv_row(
                f"paged_attention/{name}_R{R}", dt,
                f"descriptors={stats['descriptors']};pages={stats['pages']};"
                f"dma_reduction={stats['reduction']:.2f}x;maxerr={err:.1e};"
                f"device={device.device_kind}"))
    return out


if __name__ == "__main__":
    for line in main():
        print(line)
