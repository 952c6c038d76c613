"""Pallas TPU paged decode attention with run-coalesced DMA.

The RDMAbox idea inside the chip: the host-side planner (ops.plan_blocks)
is the merge queue — it turns each sequence's page list into maximal
contiguous runs and chops them into fixed-size blocks of R pages. The
kernel issues ONE async copy per block (R pages in a single DMA) instead
of one per page — batching-on-MR at the HBM→VMEM tier. When the allocator
preserved contiguity, every block carries R valid pages (full descriptor
reduction); a fragmented cache degrades gracefully to valid=1 blocks
(single-page copies), which is exactly load-aware batching's
no-forced-merging behaviour.

Completion handling is the kernel analogue of Adaptive Polling: the DMA
semaphore is waited on only when the next block's buffer is needed
(event-triggered), and the double buffer drains bursts without stalls.

Lane packing: the TPU tiles the minor dimension in 128 lanes, so a head
width D < 128 cannot be sliced out of the pool on its own. The pool is
viewed with heads folded into the minor axis (a free reshape of the
public layout), and ``c = 128 // D`` kv heads share one 128-lane chunk.
Each chunk's queries arrive block-diagonal (query rows of kv head i carry
their values only in lanes i·D..(i+1)·D), so one plain matmul per chunk
gives every head's scores; the P·V product's off-diagonal lanes are
dropped by the wrapper.

Layouts:
  q_bd:       (B, nC, c·G, c·D)  block-diagonal queries per lane chunk
  kv_pages:   (P, T, 2·Kh·D)     per token: k of all heads, then v
  block_start:(B, NB)  s32       first page id of each R-page block
  block_valid:(B, NB)  s32       valid pages in the block (0 = skip)
  lengths:    (B,)     s32       tokens in the sequence
  out:        (B, nC, c·G, c·D)  diagonal blocks hold each head's output
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def heads_per_chunk(head_dim: int, kv_heads: int) -> int:
    """kv heads packed into one 128-lane chunk (1 when D ≥ 128)."""
    if head_dim % LANES == 0:
        return 1
    if LANES % head_dim or kv_heads % (LANES // head_dim):
        raise ValueError(
            f"head_dim={head_dim} with {kv_heads} kv heads cannot be packed "
            f"into {LANES}-lane chunks")
    return LANES // head_dim


def _kernel(block_start, block_valid, lengths,      # scalar prefetch (SMEM)
            q_ref, kv_hbm, o_ref,                   # tensor refs
            kv_buf, sem,                             # scratch: double buffer
            *, pages_per_block: int, num_blocks: int, page_tokens: int,
            head_dim: int):
    b = pl.program_id(0)
    R, T = pages_per_block, page_tokens
    nC, rows, cw = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    F = kv_buf.shape[3] // 2                         # Kh·D: v's lane offset
    scale = head_dim ** -0.5
    seq_len = lengths[b]

    def dma(i, slot):
        start = block_start[b, i]
        return pltpu.make_async_copy(
            kv_hbm.at[pl.ds(start, R)], kv_buf.at[slot], sem.at[slot])

    # warm-up: kick off block 0 into slot 0
    @pl.when(block_valid[b, 0] > 0)
    def _():
        dma(0, 0).start()

    def block_step(i, carry):
        ms, ls, accs, cnt = carry
        slot = jax.lax.rem(i, 2)
        nvalid = block_valid[b, i]

        # adaptive-polling analogue: prefetch block i+1 into the other
        # buffer before waiting on block i (overlap compute with DMA)
        @pl.when(jnp.logical_and(i + 1 < num_blocks,
                                 block_valid[b, i + 1] > 0))
        def _():
            dma(i + 1, 1 - slot).start()

        @pl.when(nvalid > 0)
        def _():
            dma(i, slot).wait()

        tok = jax.lax.broadcasted_iota(jnp.int32, (rows, R * T), 1)
        base = cnt * T                    # cumulative token offset: blocks
        valid = (tok < nvalid * T) & (base + tok < seq_len)  # may be < R pages
        # blocks with nvalid == 0 contribute nothing (s = -inf everywhere
        # would corrupt m); guard by selecting the old carry
        keep = nvalid > 0
        out_m, out_l, out_acc = [], [], []
        for j in range(nC):
            k = kv_buf[slot, :, :, pl.ds(j * cw, cw)].reshape(R * T, cw)
            v = kv_buf[slot, :, :, pl.ds(F + j * cw, cw)].reshape(R * T, cw)
            s = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m, l, acc = ms[j], ls[j], accs[j]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1, keepdims=True)
            acc_new = acc * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out_m.append(jnp.where(keep, m_new, m))
            out_l.append(jnp.where(keep, l_new, l))
            out_acc.append(jnp.where(keep, acc_new, acc))
        return tuple(out_m), tuple(out_l), tuple(out_acc), cnt + nvalid

    m0 = tuple(jnp.full((rows, 1), NEG_INF, jnp.float32) for _ in range(nC))
    l0 = tuple(jnp.zeros((rows, 1), jnp.float32) for _ in range(nC))
    a0 = tuple(jnp.zeros((rows, cw), jnp.float32) for _ in range(nC))
    _, ls, accs, _ = jax.lax.fori_loop(0, num_blocks, block_step,
                                       (m0, l0, a0, jnp.int32(0)))
    for j in range(nC):
        out = accs[j] / jnp.maximum(ls[j], 1e-30)
        o_ref[0, j] = out.astype(o_ref.dtype)


def paged_attention_kernel(q: jax.Array, kv_pages: jax.Array,
                           block_start: jax.Array, block_valid: jax.Array,
                           lengths: jax.Array, *, pages_per_block: int,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, D); kv_pages: (P, T, 2, Kh, D). Returns (B, H, D)."""
    B, H, D = q.shape
    P, T, two, Kh, _ = kv_pages.shape
    assert two == 2
    NB = block_start.shape[1]
    R = pages_per_block
    G = H // Kh
    c = heads_per_chunk(D, Kh)
    nC, rows, cw = Kh // c, c * G, c * D

    # block-diagonal queries: head (j·c + i)·G + g keeps its D values in
    # lanes i·D..(i+1)·D of chunk j and zeros elsewhere
    eye = jnp.eye(c, dtype=bool)
    qr = q.reshape(B, nC, c, G, 1, D).astype(kv_pages.dtype)
    q_bd = jnp.where(eye[:, None, :, None], qr, 0)   # (B, nC, c, G, c, D)
    q_bd = q_bd.reshape(B, nC, rows, cw)
    pool = kv_pages.reshape(P, T, 2 * Kh * D)        # free: heads into lanes

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, nC, rows, cw), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # kv pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, nC, rows, cw), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, R, T, 2 * Kh * D), kv_pages.dtype),  # double buf
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_kernel, pages_per_block=R, num_blocks=NB,
                               page_tokens=T, head_dim=D)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nC, rows, cw), q.dtype),
        interpret=interpret,
    )(block_start, block_valid, lengths, q_bd, pool)
    # keep each head's own lanes: the diagonal of the (c, c) head blocks
    out = jnp.diagonal(out.reshape(B, nC, c, G, c, D), axis1=2, axis2=4)
    return jnp.moveaxis(out, -1, 2).reshape(B, H, D)   # (B, nC, c, G, D)
