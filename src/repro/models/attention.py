"""Grouped-query attention with sliding windows, softcaps, and KV caches.

Covers every attention variant among the assigned architectures:
  - MHA / GQA / MQA via ``n_kv_heads`` (paligemma: kv=1).
  - gemma2/3 interleaved local (sliding-window) and global layers: the window
    is a *static per-layer* parameter; ``window >= seq`` means global.
  - gemma2 attention-logit softcap.
  - partial rotary (stablelm), configurable rope theta, head_dim != d/heads
    (gemma-7b head_dim=256).

Three entry points share one core:
  - ``attn_train``: full-sequence causal attention (training / scoring).
  - ``attn_prefill``: same, but also returns the populated KV cache.
  - ``attn_decode``: single-token step against a pre-allocated cache, one
    layer's or the decode layer scan's stacked carry.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope
from repro.runtime.sharding import constrain

Params = Any

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static per-layer attention hyperparameters."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0          # 0 -> global causal; >0 -> sliding window
    softcap: float = 0.0
    query_scale: float = 0.0  # 0 -> rsqrt(head_dim)
    rope_theta: float = 10_000.0
    rope_pct: float = 1.0

    @classmethod
    def from_config(cls, cfg: ModelConfig, *, local: bool) -> "AttnSpec":
        return cls(
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim,
            window=cfg.sliding_window if local else 0,
            softcap=cfg.attn_softcap,
            query_scale=cfg.query_scale,
            rope_theta=cfg.rope_theta,
            rope_pct=cfg.rope_pct,
        )


def init_attn(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = 1.0 / jnp.sqrt(d)
    return {
        "wq": jax.random.normal(kq, (d, cfg.n_heads, hd), dtype) * s,
        "wk": jax.random.normal(kk, (d, cfg.n_kv_heads, hd), dtype) * s,
        "wv": jax.random.normal(kv, (d, cfg.n_kv_heads, hd), dtype) * s,
        "wo": jax.random.normal(ko, (cfg.n_heads, hd, d), dtype) * s,
    }


def _qkv(params: Params, x: jax.Array, positions: jax.Array, spec: AttnSpec):
    dtype = x.dtype
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(dtype))
    q = apply_rope(q, positions, theta=spec.rope_theta, rope_pct=spec.rope_pct)
    k = apply_rope(k, positions, theta=spec.rope_theta, rope_pct=spec.rope_pct)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(v, "batch", "seq", "heads", None)
    return q, k, v


def _scale(spec: AttnSpec) -> float:
    return spec.query_scale if spec.query_scale else spec.head_dim**-0.5


def _sdpa(
    q: jax.Array,          # (b, sq, n, h)
    k: jax.Array,          # (b, sk, nk, h)
    v: jax.Array,          # (b, sk, nk, h)
    mask: jax.Array,       # (b or 1, sq, sk) boolean, True = attend
    spec: AttnSpec,
) -> jax.Array:
    b, sq, n, h = q.shape
    group = spec.n_heads // spec.n_kv_heads
    qg = q.reshape(b, sq, spec.n_kv_heads, group, h)
    logits = jnp.einsum("bsngh,btnh->bngst", qg * _scale(spec), k).astype(jnp.float32)
    if spec.softcap:
        logits = spec.softcap * jnp.tanh(logits / spec.softcap)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v)
    return out.reshape(b, sq, n, h)


def causal_mask(sq: int, sk: int, q_offset, window: int) -> jax.Array:
    """(1, sq, sk) mask: key t attends iff t <= q_pos and q_pos - t < window."""
    q_pos = jnp.arange(sq) + q_offset
    k_pos = jnp.arange(sk)
    m = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m[None]


def attn_train(
    params: Params,
    x: jax.Array,
    spec: AttnSpec,
    positions: Optional[jax.Array] = None,
    *,
    use_flash: bool = False,
) -> jax.Array:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q, k, v = _qkv(params, x, positions, spec)
    if use_flash:
        # Fused Pallas path (TPU target; interpret-mode on CPU): the
        # populate/prefill hot spot never materialises the (S, S) scores.
        from repro.kernels.flash_attn.ops import flash_attention

        out = flash_attention(
            jnp.swapaxes(q, 1, 2),
            jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2),
            window=spec.window,
            softcap=spec.softcap,
            scale=_scale(spec),
        )
        out = jnp.swapaxes(out, 1, 2)
    else:
        mask = causal_mask(s, s, 0, spec.window)
        out = _sdpa(q, k, v, mask, spec)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# KV cache (serving)
# ---------------------------------------------------------------------------


def init_kv_cache(
    batch: int, max_seq: int, spec: AttnSpec, dtype=jnp.bfloat16
) -> dict[str, jax.Array]:
    shape = (batch, max_seq, spec.n_kv_heads, spec.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def attn_prefill(
    params: Params, x: jax.Array, spec: AttnSpec, cache: dict[str, jax.Array]
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Full-sequence forward that also writes positions [0, s) of the cache."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q, k, v = _qkv(params, x, positions, spec)
    mask = causal_mask(s, s, 0, spec.window)
    out = _sdpa(q, k, v, mask, spec)
    new_cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
    }
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))
    return y, new_cache


def attn_prefill_ext(
    params: Params,
    x: jax.Array,                 # (b, s, d) tail tokens (right-padded)
    offs: jax.Array,              # (b,) int32 per-row start position
    spec: AttnSpec,
    cache: dict[str, jax.Array],
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Per-row *offset* prefill: row b's ``s`` tokens occupy positions
    ``[offs[b], offs[b] + s)``; K/V scatter into the cache at those
    positions and attention runs over the CACHE — including whatever the
    caller pre-wrote below ``offs`` (the prefix-reuse admission path:
    gathered pool blocks sit at ``[0, offs)``, so the tail attends reused
    keys without recomputing them; the cache and compute dtypes coincide,
    so a cached key is bitwise the key a dense prefill would recompute).

    Padding doctrine matches ``sched_prefill``: pad tail positions write
    garbage K/V at indices >= the row's true end (``mode="drop"`` for
    writes past the cache) and rows with a shorter reused prefix see
    garbage between their prefix and the wave's padded prefix — all at
    positions >= their own length, which the causal mask hides and decode
    overwrites before ever attending."""
    b, s, _ = x.shape
    positions = (
        offs[:, None].astype(jnp.int32)
        + jnp.arange(s, dtype=jnp.int32)[None]
    )                                                        # (b, s)
    q, k, v = _qkv(params, x, positions, spec)
    rows = jnp.arange(b)[:, None]
    ck = cache["k"].at[rows, positions].set(
        k.astype(cache["k"].dtype), mode="drop"
    )
    cv = cache["v"].at[rows, positions].set(
        v.astype(cache["v"].dtype), mode="drop"
    )
    sk = ck.shape[1]
    k_pos = jnp.arange(sk)
    mask = k_pos[None, None, :] <= positions[:, :, None]     # (b, s, sk)
    if spec.window > 0:
        mask &= k_pos[None, None, :] > (positions[:, :, None] - spec.window)
    out = _sdpa(q, ck.astype(x.dtype), cv.astype(x.dtype), mask, spec)
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))
    return y, {"k": ck, "v": cv}


def _write_token(cache: jax.Array, t: jax.Array, pos, layer) -> jax.Array:
    """Write one token per row, ``t`` (b, 1, nk, hd), at ``pos`` of one
    layer's (b, S, nk, hd) cache, or at ``(layer, row, pos)`` of the
    stacked (L, b, S, nk, hd) cache when ``layer`` is given."""
    t = t.astype(cache.dtype)
    if getattr(pos, "ndim", 0) > 0:
        rows = jnp.arange(t.shape[0])
        at = (rows, pos) if layer is None else (layer, rows, pos)
        return cache.at[at].set(t[:, 0])
    if layer is None:
        return jax.lax.dynamic_update_slice(cache, t, (0, pos, 0, 0))
    return jax.lax.dynamic_update_slice(cache, t[None], (layer, 0, pos, 0, 0))


def attn_decode(
    params: Params,
    x: jax.Array,                 # (b, 1, d)
    pos: jax.Array,               # scalar int32 OR (b,) int32 per-row index
    spec: AttnSpec,
    cache: dict[str, jax.Array],
    layer: Optional[jax.Array] = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One decode step: write K/V at ``pos``, then attend over the cache
    with keys at positions <= ``pos`` (and inside the window) unmasked.

    ``pos`` is a scalar on the classic whole-batch path (every row at the
    same sequence position, written by ``dynamic_update_slice``). A (b,)
    vector selects the continuous-batching path: each row scatters its
    K/V at its own position and masks keys per row — what the request
    scheduler needs once rows admitted at different times share one live
    batch.

    ``cache`` holds one layer's (b, S, nk, hd) K/V, or, with a ``layer``
    index, the whole stacked (L, b, S, nk, hd) K/V that the decode layer
    scan carries: the token is written in place at ``(layer, row, pos)``
    and the layer is read straight from the carry, so a step moves one
    token per row instead of copying the layer out and back. Returns
    ``(y, cache)`` with the cache in the form it was given."""
    b = x.shape[0]
    positions = jnp.broadcast_to(
        jnp.reshape(pos, (-1, 1)), (b, 1)).astype(jnp.int32)     # (b, 1)
    q, k, v = _qkv(params, x, positions, spec)
    new_cache = {n: _write_token(cache[n], t, pos, layer)
                 for n, t in (("k", k), ("v", v))}
    if layer is None:
        ck, cv = new_cache["k"], new_cache["v"]
    else:
        new_cache = {n: constrain(c, None, "batch", "seq", "heads", None)
                     for n, c in new_cache.items()}
        ck, cv = (jax.lax.dynamic_index_in_dim(new_cache[n], layer, 0, False)
                  for n in ("k", "v"))
    k_pos = jnp.arange(ck.shape[1])
    mask = k_pos[None, None, :] <= positions[:, :, None]         # (b, 1, sk)
    if spec.window > 0:
        mask &= k_pos[None, None, :] > (positions[:, :, None] - spec.window)
    out = _sdpa(q, ck.astype(x.dtype), cv.astype(x.dtype), mask, spec)
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))
    return y, new_cache


def attn_decode_paged(
    params: Params,
    x: jax.Array,                 # (b, 1, d)
    pos: jax.Array,               # (b,) int32 per-row position
    spec: AttnSpec,
    pool: dict[str, jax.Array],   # {"k","v"}: (n_blocks, block, n_kv, hd)
    table: dict[str, Any] | jax.Array,  # (b, T) int32 pool block ids
    *,
    use_kernel: bool = False,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Block-table variant of ``attn_decode``'s vector branch: row ``b``'s
    KV for token position ``t`` lives in pool block ``table[b, t //
    block]`` at offset ``t % block`` instead of a contiguous cache row.

    The step writes the new K/V at ``pos`` into the owning pool block —
    the caller must hold that block EXCLUSIVELY (the copy-on-write rule:
    ``KVBlockPool.copy_block`` first if shared) — then attends over the
    gathered per-row keys with the same per-row causal mask as the dense
    branch, so given equal KV bytes the output is bitwise the dense
    ``attn_decode``'s (tested). ``use_kernel`` routes the gather through
    the Pallas scalar-prefetch kernel (interpret off-TPU); either way the
    gather is pure data movement. Returns (y, updated pool)."""
    from repro.kernels.flash_attn import paged

    b = x.shape[0]
    blk = pool["k"].shape[1]
    positions = pos[:, None].astype(jnp.int32)               # (b, 1)
    q, k, v = _qkv(params, x, positions, spec)
    owner = jnp.take_along_axis(
        table, (pos // blk)[:, None].astype(table.dtype), axis=1
    )[:, 0]                                                  # (b,)
    off = pos % blk
    new_pool = {
        "k": pool["k"].at[owner, off].set(k[:, 0].astype(pool["k"].dtype)),
        "v": pool["v"].at[owner, off].set(v[:, 0].astype(pool["v"].dtype)),
    }
    ck = paged.gather(new_pool["k"], table, use_kernel=use_kernel)
    cv = paged.gather(new_pool["v"], table, use_kernel=use_kernel)
    sk = ck.shape[1]
    k_pos = jnp.arange(sk)
    mask = k_pos[None, None, :] <= pos[:, None, None]        # (b, 1, sk)
    if spec.window > 0:
        mask &= k_pos[None, None, :] > (pos[:, None, None] - spec.window)
    out = _sdpa(q, ck.astype(x.dtype), cv.astype(x.dtype), mask, spec)
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))
    return y, new_pool
