"""Pallas TPU flash attention: online-softmax over KV blocks in VMEM.

Forward-only fusion for the backbone's populate/prefill pass (the paper's
epoch-0 cost): never materialises the (S, S) score matrix. Supports causal
masking, gemma-style sliding windows (local layers), GQA (kv-head folding),
and gemma2 logit softcaps.

Grid (B*H, S/BQ, S/BK) with the KV axis innermost ("arbitrary"): the fp32
accumulator, running max m and normaliser l live in VMEM scratch and are
carried across KV steps; the output block is written on the last KV step.
Sliding windows make most KV blocks fully masked for large S — those steps
exit early via ``pl.when`` (block-level skipping; with BQ=BK=128 and window
1024, a 32k-prefill local layer touches ~9/256 of the KV blocks).

VMEM per step (BQ=BK=128, hd<=256, bf16): q/k/v blocks 3*64 KB + fp32 acc
128x256x4 = 128 KB + scores 64 KB << 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 128
BK = 128
NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, *, scale, window, softcap, s_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    q_start = qi * BQ
    k_start = ki * BK

    # Block-level reachability: causal + window (traced on grid indices).
    # Any query row in [q_start, q_start+BQ) can see key col c iff
    # c <= row and c > row - window.
    reachable = k_start <= q_start + BQ - 1
    if window > 0:  # static hyperparameter
        reachable = jnp.logical_and(
            reachable, k_start + BK - 1 >= q_start - (window - 1)
        )

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0]  # (BQ, hd)
        k = k_ref[0]  # (BK, hd)
        v = v_ref[0]  # (BK, hd)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                           # (BQ, BK)
        if softcap:
            scores = softcap * jnp.tanh(scores / softcap)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        mask = cols <= rows
        if window > 0:
            mask &= cols > rows - window
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_sc[...]                                  # (BQ,)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1))
        alpha = jnp.exp(m_prev - m_new)                     # (BQ,)
        p = jnp.exp(scores - m_new[:, None])                # (BQ, BK)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "interpret")
)
def flash_attention_fwd(
    q: jax.Array,   # (BH, S, hd) — batch*heads folded
    k: jax.Array,   # (BH, S, hd) — kv heads pre-broadcast to BH
    v: jax.Array,
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    bh, s, hd = q.shape
    assert s % BQ == 0 and s % BK == 0, f"seq {s} must be a multiple of {BQ}"
    scale = scale if scale is not None else hd**-0.5
    grid = (bh, s // BQ, s // BK)
    kernel = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap, s_len=s
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, BK, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, BK, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, hd), jnp.float32),
            pltpu.VMEM((BQ,), jnp.float32),
            pltpu.VMEM((BQ,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
