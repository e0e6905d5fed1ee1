"""Paged KV-cache block gather: Pallas kernel + jnp oracle.

The paged KV pool (``core.kv_pool``) stores key/value blocks as
``(n_blocks, block, n_kv, head_dim)``; a request addresses its prefix
through an ordered *block table* of pool ids. The gather materialises a
batch of tables into contiguous per-row K/V — the admission path's
"zero prefill FLOPs" move: reused prefix keys are copied, never
recomputed.

Two implementations with one contract (bitwise equal — this is data
movement, not arithmetic, so there is nothing to drift):

  - ``paged_gather_ref``: ``jnp.take`` oracle. Fuses into the
    surrounding admission jit; the CPU/default path.
  - ``paged_gather``: Pallas kernel with the block table scalar-prefetched
    (``PrefetchScalarGridSpec``), so on TPU each grid step DMAs exactly
    one pool block HBM->VMEM with its index known before the body runs —
    the same trick the grouped skip-LoRA kernels use for slot tiling.
    Off-TPU it runs in interpret mode (tests assert kernel == oracle).

``models.attention.attn_decode_paged`` builds the block-table decode
variant on top of these.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def paged_gather_ref(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Oracle: pool (NB, block, n_kv, hd) + tables (B, T) int32 ->
    (B, T * block, n_kv, hd). Table entries must be valid pool ids; rows
    that own fewer than T blocks pad with any valid id (the caller masks
    the padded positions out of attention)."""
    b, t = tables.shape
    nb, blk, nkv, hd = pool.shape
    out = jnp.take(pool, tables.reshape(-1), axis=0)
    return out.reshape(b, t * blk, nkv, hd)


def _gather_kernel(tbl_ref, pool_ref, out_ref):
    del tbl_ref  # consumed by the index maps; the body sees the gathered block
    out_ref[0, 0] = pool_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather(
    pool: jax.Array,          # (NB, block, n_kv, hd)
    tables: jax.Array,        # (B, T) int32 pool block ids
    *,
    interpret: bool = False,
) -> jax.Array:
    """Pallas block-table gather; same contract as ``paged_gather_ref``.

    Grid = (rows, table slots); the table rides in as the scalar-prefetch
    operand so the input BlockSpec's index map selects pool block
    ``tables[b, j]`` for grid step (b, j) — one block copy per step, no
    dynamic indexing inside the body."""
    b, t = tables.shape
    nb, blk, nkv, hd = pool.shape
    d = nkv * hd
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, t),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda bi, ji, tbl: (tbl[bi, ji], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk, d), lambda bi, ji, tbl: (bi, ji, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, blk, d), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(tables.astype(jnp.int32), pool.reshape(nb, blk, d))
    return out.reshape(b, t * blk, nkv, hd)


def gather(pool: jax.Array, tables: jax.Array, *, use_kernel: bool = False) -> jax.Array:
    """Dispatch helper for the serve path: the Pallas kernel on real TPU,
    the fusing oracle everywhere else. Unlike the grouped skip-LoRA
    wrappers this does NOT fall back to interpret mode off-TPU — an
    interpreted per-block grid walk is orders of magnitude slower than
    the ``jnp.take`` oracle it is bitwise-equal to, and the admission
    dispatch is latency-critical. Interpret-mode kernel parity is covered
    by tests calling ``paged_gather(..., interpret=True)`` directly."""
    if use_kernel and not _interpret():
        return paged_gather(pool, tables)
    return paged_gather_ref(pool, tables)
