"""Host-side planner + jit wrapper for paged decode attention.

``plan_blocks`` is the merge queue of the kernel tier: page lists →
contiguous runs → fixed-R-page DMA block descriptors. ``paged_attention``
is the public entry point; ``pages_per_block=1`` degenerates to the
uncoalesced per-page baseline the benchmark compares against.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import numpy as np

from ...memory.kv_cache import plan_page_runs
from .kernel import paged_attention_kernel


def plan_blocks(page_table: np.ndarray, pages_per_block: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(B, Pmax) page table (−1 padded) → (block_start, block_valid).

    Runs are chopped into blocks of ≤ R pages, in sequence order. The
    number of descriptors per sequence is NB = ceil(Pmax / R) at worst;
    contiguity makes most blocks carry R valid pages.
    """
    B, Pmax = page_table.shape
    R = pages_per_block
    NB = Pmax                     # worst case: fully fragmented, 1 page/block
    starts = np.zeros((B, NB), np.int32)
    valid = np.zeros((B, NB), np.int32)
    for b in range(B):
        pages = [int(p) for p in page_table[b] if p >= 0]
        blocks = []
        for run in plan_page_runs(pages):
            s, n = run.start, run.length
            while n > 0:
                take = min(n, R)
                blocks.append((s, take))
                s += take
                n -= take
        for i, (s, n) in enumerate(blocks):
            starts[b, i] = s
            valid[b, i] = n
    return starts, valid


def descriptor_stats(page_table: np.ndarray, pages_per_block: int) -> dict:
    """How many DMA descriptors the planner emits vs per-page baseline."""
    _, valid = plan_blocks(page_table, pages_per_block)
    pages = int((page_table >= 0).sum())
    descs = int((valid > 0).sum())
    return {"pages": pages, "descriptors": descs,
            "reduction": pages / max(descs, 1)}


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_attention_blocks(q, kv_pages, block_start, block_valid, lengths, *,
                           pages_per_block: int, interpret: bool = False):
    """The jitted kernel call on an already-planned block table."""
    return paged_attention_kernel(
        q, kv_pages, block_start, block_valid, lengths,
        pages_per_block=pages_per_block, interpret=interpret)


def paged_attention(q: jax.Array, kv_pages: jax.Array,
                    page_table: np.ndarray, lengths: jax.Array,
                    *, pages_per_block: int = 4,
                    interpret: bool = False) -> jax.Array:
    starts, valid = plan_blocks(np.asarray(page_table), pages_per_block)
    # An R-page DMA may over-read up to R-1 pages past a run; a production
    # pool allocates R-1 slack pages at the end. Pad here so dynamic_slice
    # never clamps (clamping would SHIFT the window and corrupt data).
    R = pages_per_block
    if R > 1:
        pad = [(0, R - 1)] + [(0, 0)] * (kv_pages.ndim - 1)
        kv_pages = jax.numpy.pad(kv_pages, pad)
    return paged_attention_blocks(q, kv_pages, jax.numpy.asarray(starts),
                                  jax.numpy.asarray(valid), lengths,
                                  pages_per_block=pages_per_block,
                                  interpret=interpret)
