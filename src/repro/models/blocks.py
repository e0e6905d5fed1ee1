"""Per-layer blocks and the periodic LayerStack.

A *block* is one residual layer: pre-norm -> mixer (attention / mamba /
mLSTM / sLSTM) -> residual add, then (for attention/mamba layers) pre-norm ->
FFN-or-MoE -> residual add. gemma2/3 sandwich post-norms are supported.

The *LayerStack* tiles ``cfg.pattern`` ``n_periods`` times via ``lax.scan``
(params stacked on a leading periods axis, one compiled body per period) plus
an unrolled remainder. The stack also implements the Skip-LoRA tap: when
adapter params are passed, every block's *input* hidden state is projected
through its (A_k, B_k) pair and accumulated into a running skip term that the
LM adds to the final hidden state (Eq. 17 of the paper, at LM scale).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import ssm as S
from repro.models.config import ModelConfig
from repro.models.ffn import ffn, init_ffn
from repro.models.layers import apply_norm, make_norm
from repro.models.moe import init_moe, moe_ffn
from repro.runtime.sharding import constrain

Params = Any

ATTN_KINDS = ("attn", "attn_local")

# Dry-run control: unroll the period scan so HLO cost analysis sees every
# layer (lax.scan lowers to a while loop whose body XLA counts only once).
_SCAN_UNROLL: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_scan_unroll", default=False
)


@contextlib.contextmanager
def scan_unroll_scope(enabled: bool = True):
    tok = _SCAN_UNROLL.set(enabled)
    try:
        yield
    finally:
        _SCAN_UNROLL.reset(tok)


def _norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    return apply_norm(
        cfg.norm_type, p, x, eps=cfg.norm_eps, unit_offset=cfg.rmsnorm_unit_offset
    )


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------


def init_block(key: jax.Array, kind: str, layer_idx: int, cfg: ModelConfig, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    d = cfg.d_model
    p: dict[str, Params] = {"norm1": make_norm(cfg.norm_type, d)}
    if kind in ATTN_KINDS:
        p["attn"] = A.init_attn(k1, cfg, dtype)
    elif kind == "mamba":
        p["mamba"] = S.init_mamba(k1, cfg, dtype)
    elif kind == "mlstm":
        p["mlstm"] = S.init_mlstm(k1, cfg, dtype)
    elif kind == "slstm":
        p["slstm"] = S.init_slstm(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    if cfg.use_post_norm:
        p["post_norm1"] = make_norm(cfg.norm_type, d)
    # External FFN sublayer (attention and mamba blocks; xLSTM cells have
    # their own internal projections).
    if kind in ATTN_KINDS or kind == "mamba":
        has_moe = cfg.layer_has_moe(layer_idx)
        if has_moe:
            p["norm2"] = make_norm(cfg.norm_type, d)
            p["moe"] = init_moe(k2, d, cfg.moe, dtype)
        elif cfg.d_ff:
            p["norm2"] = make_norm(cfg.norm_type, d)
            p["ffn"] = init_ffn(k2, d, cfg.d_ff, gated=cfg.ffn_gated, dtype=dtype)
        if cfg.use_post_norm and ("moe" in p or "ffn" in p):
            p["post_norm2"] = make_norm(cfg.norm_type, d)
    return p


def init_block_cache(
    kind: str, batch: int, max_seq: int, cfg: ModelConfig, dtype
) -> Optional[Params]:
    if kind in ATTN_KINDS:
        spec = A.AttnSpec.from_config(cfg, local=(kind == "attn_local"))
        return A.init_kv_cache(batch, max_seq, spec, dtype)
    if kind == "mamba":
        return S.init_mamba_state(batch, cfg, dtype)
    if kind == "mlstm":
        return S.init_mlstm_state(batch, cfg, dtype)
    if kind == "slstm":
        return S.init_slstm_state(batch, cfg, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------


def block_forward(
    kind: str,
    params: Params,
    h: jax.Array,
    cfg: ModelConfig,
    *,
    mode: str,                     # "train" | "prefill" | "decode"
    cache: Optional[Params] = None,
    pos: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[Params], jax.Array]:
    """Apply one block. Returns (h_out, new_cache, moe_aux_loss).

    ``layer`` (decode, attention kinds) marks ``cache`` as the stacked
    (L, ...) K/V of the block's period position; see ``A.attn_decode``."""
    aux = jnp.zeros((), jnp.float32)
    x = _norm(cfg, params["norm1"], h)
    new_cache = None

    if kind in ATTN_KINDS:
        spec = A.AttnSpec.from_config(cfg, local=(kind == "attn_local"))
        if mode == "train":
            y = A.attn_train(params["attn"], x, spec)
        elif mode == "prefill":
            if pos is not None:
                # Offset prefill (prefix-reuse admission): ``pos`` is the
                # (b,) per-row start position; the cache below it already
                # holds the reused prefix K/V.
                y, new_cache = A.attn_prefill_ext(
                    params["attn"], x, pos, spec, cache
                )
            else:
                y, new_cache = A.attn_prefill(params["attn"], x, spec, cache)
        else:
            y, new_cache = A.attn_decode(
                params["attn"], x, pos, spec, cache, layer
            )
    elif kind == "mamba":
        if mode == "train":
            y, _ = S.mamba_seq(params["mamba"], x, cfg, None)
        elif mode == "prefill":
            y, new_cache = S.mamba_seq(params["mamba"], x, cfg, cache)
        else:
            y, new_cache = S.mamba_step(params["mamba"], x, cfg, cache)
    elif kind == "mlstm":
        if mode == "train":
            y, _ = S.mlstm_seq(params["mlstm"], x, cfg, None)
        elif mode == "prefill":
            y, new_cache = S.mlstm_seq(params["mlstm"], x, cfg, cache)
        else:
            y, new_cache = S.mlstm_step(params["mlstm"], x, cfg, cache)
    elif kind == "slstm":
        if mode == "train":
            y, _ = S.slstm_seq(params["slstm"], x, cfg, None)
        elif mode == "prefill":
            y, new_cache = S.slstm_seq(params["slstm"], x, cfg, cache)
        else:
            y, new_cache = S.slstm_step(params["slstm"], x, cfg, cache)
    else:
        raise ValueError(kind)

    if cfg.use_post_norm and "post_norm1" in params:
        y = _norm(cfg, params["post_norm1"], y)
    h = h + y

    if "moe" in params:
        z = _norm(cfg, params["norm2"], h)
        y2, aux = moe_ffn(params["moe"], z, cfg.moe, act=cfg.ffn_activation)
        if cfg.use_post_norm and "post_norm2" in params:
            y2 = _norm(cfg, params["post_norm2"], y2)
        h = h + y2
    elif "ffn" in params:
        z = _norm(cfg, params["norm2"], h)
        y2 = ffn(params["ffn"], z, act=cfg.ffn_activation, gated=cfg.ffn_gated)
        if cfg.use_post_norm and "post_norm2" in params:
            y2 = _norm(cfg, params["post_norm2"], y2)
        h = h + y2

    return h, new_cache, aux


# ---------------------------------------------------------------------------
# LayerStack: periodic scan + remainder
# ---------------------------------------------------------------------------


def init_stack(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    """Params: {"periods": [per-position stacked pytrees], "remainder": [...]}"""
    kinds = cfg.layer_kinds()
    keys = jax.random.split(key, len(kinds))
    per_layer = [
        init_block(keys[i], kinds[i], i, cfg, dtype) for i in range(len(kinds))
    ]
    n_per, period = cfg.n_periods, cfg.period
    periods = []
    for pos in range(period):
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0),
            *[per_layer[p * period + pos] for p in range(n_per)],
        )
        periods.append(stacked)
    remainder = per_layer[n_per * period :]
    return {"periods": periods, "remainder": remainder}


def init_stack_caches(
    batch: int, max_seq: int, cfg: ModelConfig, dtype
) -> Params:
    """Caches in the same periods/remainder layout as the params."""
    kinds = cfg.layer_kinds()
    per_layer = [
        init_block_cache(kinds[i], batch, max_seq, cfg, dtype)
        for i in range(len(kinds))
    ]
    n_per, period = cfg.n_periods, cfg.period
    periods = []
    for pos in range(period):
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0),
            *[per_layer[p * period + pos] for p in range(n_per)],
        )
        periods.append(stacked)
    return {"periods": periods, "remainder": per_layer[n_per * period :]}


def _apply_adapter(adapter: Params, h: jax.Array) -> jax.Array:
    """Skip-LoRA tap: (h @ A) @ B in model dtype."""
    return (h @ adapter["A"].astype(h.dtype)) @ adapter["B"].astype(h.dtype)


def stack_forward(
    stack: Params,
    h: jax.Array,
    cfg: ModelConfig,
    *,
    mode: str,
    caches: Optional[Params] = None,
    pos: Optional[jax.Array] = None,
    adapters: Optional[Params] = None,   # {"periods": [...], "remainder": [...]}
    collect_acts: bool = False,
) -> dict[str, Any]:
    """Run all layers. Returns dict with:
    h            : final hidden state
    skip         : accumulated Skip-LoRA term (zeros if no adapters)
    caches       : updated caches (prefill/decode) or None
    acts         : per-layer block inputs (n_layers, B, S, D) if collect_acts
    aux          : summed MoE aux loss

    ``caches`` has the ``init_stack_caches`` layout. Prefill scans each
    period position's stacked caches as ``xs`` and returns them as ``ys``.
    Decode does so only for recurrent states (rewritten whole each step,
    and small); an attention position's stacked (n_periods, B, S, nk, hd)
    K/V rides in the scan's carry instead, and each layer writes its one
    new token in place at ``(layer, row, pos)`` and reads its slice from
    the carry, so no step copies a layer or the whole cache. Remainder
    layers keep their own per-layer caches.
    """
    period = cfg.period
    skip0 = jnp.zeros_like(h)
    aux0 = jnp.zeros((), jnp.float32)
    carried = [mode == "decode" and caches is not None and kind in ATTN_KINDS
               for kind in cfg.pattern]

    def period_body(carry, xs):
        hh, skip, aux, kv = carry
        p_params, p_caches, p_adapters, layer = xs
        kv = list(kv)
        new_caches = []
        acts = []
        for i, kind in enumerate(cfg.pattern):
            if collect_acts:
                acts.append(hh)
            if p_adapters is not None:
                skip = skip + _apply_adapter(p_adapters[i], hh)
            hh, c_new, a = block_forward(
                kind,
                p_params[i],
                hh,
                cfg,
                mode=mode,
                cache=kv[i] if carried[i] else p_caches[i],
                pos=pos,
                layer=layer if carried[i] else None,
            )
            hh = constrain(hh, "batch", "seq", None)
            if carried[i]:
                kv[i], c_new = c_new, None
            new_caches.append(c_new)
            aux = aux + a
        ys = (
            new_caches if mode != "train" else None,
            jnp.stack(acts, axis=0) if collect_acts else None,
        )
        return (hh, skip, aux, kv), ys

    periods = [None] * period if caches is None else caches["periods"]
    kv0 = [c if k else None for c, k in zip(periods, carried)]
    xs = (
        stack["periods"],
        [None if k else c for c, k in zip(periods, carried)],
        None if adapters is None else adapters["periods"],
        jnp.arange(cfg.n_periods) if any(carried) else None,
    )
    body = period_body
    if mode == "train":
        # Rematerialise each period in the backward pass: the scan otherwise
        # saves every block's internals (incl. attention probs) per period.
        body = jax.checkpoint(period_body)
    (h, skip, aux, kv), (period_caches, period_acts) = jax.lax.scan(
        body,
        (h, skip0, aux0, kv0),
        xs,
        unroll=cfg.n_periods if _SCAN_UNROLL.get() else 1,
    )
    if any(carried):
        period_caches = [c if k else pc
                         for c, pc, k in zip(kv, period_caches, carried)]

    # Remainder layers (unrolled).
    rem_caches = []
    rem_acts = []
    kinds = cfg.layer_kinds()
    for j, kind in enumerate(cfg.remainder_pattern):
        if collect_acts:
            rem_acts.append(h)
        if adapters is not None:
            skip = skip + _apply_adapter(adapters["remainder"][j], h)
        h, c_new, a = block_forward(
            kind,
            stack["remainder"][j],
            h,
            cfg,
            mode=mode,
            cache=None if caches is None else caches["remainder"][j],
            pos=pos,
        )
        rem_caches.append(c_new)
        aux = aux + a

    out_caches = None
    if mode != "train":
        out_caches = {"periods": period_caches, "remainder": rem_caches}

    acts = None
    if collect_acts:
        # period_acts: (n_periods, period, B, S, D) -> (L_periodic, B, S, D)
        acts = period_acts.reshape((-1,) + period_acts.shape[2:])
        if rem_acts:
            acts = jnp.concatenate([acts, jnp.stack(rem_acts, axis=0)], axis=0)

    return {"h": h, "skip": skip, "caches": out_caches, "acts": acts, "aux": aux}
