"""Per-kernel shape/dtype sweeps against the pure-jnp oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.kernel import heads_per_chunk
from repro.kernels.paged_attention.ops import (descriptor_stats,
                                               paged_attention, plan_blocks)
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan.ops import ssd_scan_op
from repro.kernels.ssd_scan.ref import ssd_ref

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Sq,Skv,H,Kh,D,causal,window,qb,kb", [
    (128, 128, 4, 2, 32, True, None, 64, 64),
    (128, 128, 4, 4, 64, False, None, 32, 64),
    (256, 256, 8, 2, 32, True, 96, 64, 32),
    (64, 192, 2, 2, 32, True, None, 32, 32),
    (64, 64, 2, 1, 128, True, None, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(Sq, Skv, H, Kh, D, causal, window, qb, kb, dtype):
    q = jnp.asarray(RNG.normal(size=(2, Sq, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(2, Skv, Kh, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(2, Skv, Kh, D)), dtype)
    out = flash_attention_op(q, k, v, causal=causal, window=window,
                             q_block=qb, kv_block=kb, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

def _random_table(B, Pmax, P, contiguous=False):
    table = -np.ones((B, Pmax), np.int32)
    for b in range(B):
        n = RNG.integers(1, Pmax + 1)
        if contiguous:
            start = RNG.integers(0, P - n)
            table[b, :n] = np.arange(start, start + n)
        else:
            table[b, :n] = RNG.choice(P, size=n, replace=False)
    return table


@pytest.mark.parametrize("R", [1, 2, 4])
@pytest.mark.parametrize("contig", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_vs_ref(R, contig, dtype):
    B, H, Kh, D, T, P, Pmax = 3, 8, 4, 32, 8, 40, 6
    q = jnp.asarray(RNG.normal(size=(B, H, D)), dtype)
    kv = jnp.asarray(RNG.normal(size=(P, T, 2, Kh, D)), dtype)
    table = _random_table(B, Pmax, P, contiguous=contig)
    npages = (table >= 0).sum(1)
    lengths = jnp.asarray(npages * T - RNG.integers(0, T, B), jnp.int32)
    out = paged_attention(q, kv, table, lengths, pages_per_block=R,
                          interpret=True)
    ref = paged_attention_ref(q, kv, jnp.asarray(table), lengths)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("H,Kh,D", [
    (16, 16, 64),      # qwen1.5-0.5b: two heads share a 128-lane chunk
    (12, 4, 64),       # rdmabox-paper-100m: GQA, G=3 query rows per head
    (8, 2, 128),       # one head per chunk
])
def test_paged_attention_lane_packing_vs_ref(H, Kh, D):
    B, T, P, Pmax = 2, 16, 24, 5
    q = jnp.asarray(RNG.normal(size=(B, H, D)), jnp.float32)
    kv = jnp.asarray(RNG.normal(size=(P, T, 2, Kh, D)), jnp.float32)
    table = _random_table(B, Pmax, P)
    lengths = jnp.asarray((table >= 0).sum(1) * T - 3, jnp.int32)
    out = paged_attention(q, kv, table, lengths, pages_per_block=2,
                          interpret=True)
    ref = paged_attention_ref(q, kv, jnp.asarray(table), lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_heads_per_chunk_rejects_unpackable_widths():
    assert heads_per_chunk(64, 16) == 2 and heads_per_chunk(256, 3) == 1
    with pytest.raises(ValueError):
        heads_per_chunk(48, 8)          # 128 is not a multiple of 48
    with pytest.raises(ValueError):
        heads_per_chunk(32, 2)          # 4 heads per chunk, only 2 exist


def test_planner_coalesces_contiguous():
    table = np.array([[0, 1, 2, 3, 4, 5, 6, 7]], np.int32)
    stats = descriptor_stats(table, 4)
    assert stats["descriptors"] == 2 and stats["reduction"] == 4.0


def test_planner_fragmented_degrades_gracefully():
    table = np.array([[0, 2, 4, 6, 8, 10, 12, 14]], np.int32)
    starts, valid = plan_blocks(table, 4)
    assert (valid[0] > 0).sum() == 8        # one descriptor per page
    assert (valid[0][valid[0] > 0] == 1).all()


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,H,P,N,chunk", [
    (2, 128, 3, 16, 8, 32),
    (1, 64, 2, 32, 16, 64),
    (2, 96, 4, 8, 4, 16),
    (1, 256, 1, 64, 32, 64),
])
def test_ssd_vs_ref(B, L, H, P, N, chunk):
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32) * 0.5
    Bm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32) * 0.5
    Cm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32) * 0.5
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, L, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    out = ssd_scan_op(x, Bm, Cm, dt, A, chunk=chunk, interpret=True)
    ref = ssd_ref(x, Bm, Cm, dt, A)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_ssd_state_continuity_across_chunks():
    """Splitting L into more chunks must not change the result."""
    B, L, H, P, N = 1, 128, 2, 8, 4
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32) * 0.5
    Bm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32) * 0.5
    Cm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32) * 0.5
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, L, H)), jnp.float32)
    A = -jnp.ones((H,), jnp.float32)
    a = ssd_scan_op(x, Bm, Cm, dt, A, chunk=16, interpret=True)
    b = ssd_scan_op(x, Bm, Cm, dt, A, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
