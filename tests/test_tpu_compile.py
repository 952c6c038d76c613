"""The Pallas kernels compile for a described TPU v5e chip at real widths.

Nothing runs: the TPU compiler, which ships with libtpu, lowers each
kernel for a chip that is described, not attached, and refuses what the
chip would refuse (unaligned slices, block shapes off the tiling, VMEM
over-use). The topology is described inside a module-scoped fixture, so
importing this file never touches libtpu.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.paged_attention.ops import paged_attention_blocks
from repro.kernels.ssd_scan.ops import ssd_scan_op


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch,H,Kh,D", [
    ("qwen1.5-0.5b", 16, 16, 64),
    ("rdmabox-paper-100m", 12, 4, 64),
])
def test_paged_attention_compiles(sds, arch, H, Kh, D):
    B, T, R, P, NB = 8, 16, 4, 4096, 256
    compiled = paged_attention_blocks.lower(
        sds((B, H, D)), sds((P + R - 1, T, 2, Kh, D)),
        sds((B, NB), jnp.int32), sds((B, NB), jnp.int32),
        sds((B,), jnp.int32), pages_per_block=R).compile()
    assert_kernel(compiled)


def test_flash_attention_compiles(sds):
    # qwen1.5-0.5b prefill: 16 heads of 64 over 2048 tokens
    q = sds((1, 2048, 16, 64))
    assert_kernel(flash_attention_op.lower(q, q, q).compile())


def test_ssd_scan_compiles(sds):
    # mamba2-780m: 48 heads of 64, state 128, chunk 256
    B, L, H, P, N = 1, 2048, 48, 64, 128
    compiled = ssd_scan_op.lower(
        sds((B, L, H, P)), sds((B, L, N)), sds((B, L, N)),
        sds((B, L, H), jnp.float32), sds((H,), jnp.float32),
        chunk=256).compile()
    assert_kernel(compiled)
