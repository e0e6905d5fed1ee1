"""Every Pallas kernel compiles for a described TPU v5e at stablelm-1.6b width.

Nothing here runs on a chip: each test lowers one kernel for a v5e chip
that is described, not attached, and compiles it with the TPU compiler
installed alongside JAX. That finds what interpret mode cannot — block
shapes Mosaic refuses, ops it cannot lower, VMEM overflows — at no chip
time. Widths: 24 layers, d 2048, rank 8, a pool of 8 slots, 256 rows, bf16
activations, 64-wide heads.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the test workers all import
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attn import kernel as FK
from repro.kernels.flash_attn import paged as FP
from repro.kernels.skip_lora import kernel as K

L, D, R, N, M, TM = 24, 2048, 8, 8, 256, 128
HEADS, HD, S = 32, 64, 256
BLOCK, N_BLOCKS = 8, 64
BF16, F32, I8, U8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.uint8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one; keep the cache out of it.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to compile with
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lowered(name: str, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    tiles = s((M // TM,), I32)
    if name == "skip_lora_fwd":
        return K.skip_lora_fwd.lower(s((L, M, D), BF16), s((L, D, R), F32),
                                     s((L, R, D), F32))
    if name == "skip_lora_bwd":
        return K.skip_lora_bwd.lower(s((L, M, D), BF16), s((L, D, R), F32),
                                     s((L, R, D), F32), s((M, D), BF16))
    if name in ("skip_lora_grouped_fwd_ml", "skip_lora_grouped_fwd_lm"):
        return K.skip_lora_grouped_fwd.lower(
            s((L, M, D), BF16), s((N, L, D, R), F32), s((N, L, R, D), F32),
            tiles, grid_order=name[-2:],
        )
    if name == "skip_lora_grouped_bwd":
        return K.skip_lora_grouped_bwd.lower(
            s((L, M, D), BF16), s((N, L, D, R), F32), s((N, L, R, D), F32),
            s((M, D), BF16), tiles,
        )
    if name == "skip_lora_grouped_fwd_int8":
        return K.skip_lora_grouped_fwd_int8.lower(
            s((L, M, D), BF16), s((N, L, D, R), I8), s((N, L, D), F32),
            s((N, L, R, D), I8), s((N, L, R), F32), tiles,
        )
    if name == "skip_lora_grouped_fwd_q4":
        return K.skip_lora_grouped_fwd_q4.lower(
            s((L, M, D), BF16), s((N, L, D, R // 2), U8), s((N, L, D), F32),
            s((N, L, R, D // 2), U8), s((N, L, R), F32), s((1, 16), F32),
            tiles,
        )
    if name == "skip_lora_grouped_fwd_actint8":
        return K.skip_lora_grouped_fwd_actint8.lower(
            s((L, M, D), I8), s((L, M), F32), s((N, L, D, R), F32),
            s((N, L, R, D), F32), tiles,
        )
    if name == "skip_lora_fwd_int8":
        return K.skip_lora_fwd_int8.lower(
            s((L, M, D), I8), s((L, M), F32), s((L, D, R), F32),
            s((L, R, D), F32),
        )
    if name == "flash_attention_fwd":
        qkv = s((HEADS, S, HD), BF16)
        return FK.flash_attention_fwd.lower(qkv, qkv, qkv)
    if name == "paged_gather":
        return FP.paged_gather.lower(
            s((N_BLOCKS, BLOCK, HEADS, HD), BF16), s((4, S // BLOCK), I32)
        )
    raise KeyError(name)


KERNELS = [
    "skip_lora_fwd",
    "skip_lora_bwd",
    "skip_lora_grouped_fwd_ml",
    "skip_lora_grouped_fwd_lm",
    "skip_lora_grouped_bwd",
    "skip_lora_grouped_fwd_int8",
    "skip_lora_grouped_fwd_q4",
    "skip_lora_grouped_fwd_actint8",
    "skip_lora_fwd_int8",
    "flash_attention_fwd",
    "paged_gather",
]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip):
    compiled = _lowered(name, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
