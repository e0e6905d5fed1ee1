"""Skip2-LoRA at LM scale: adapters, sharded activation cache, train steps.

The paper's topology mapped onto a transformer (DESIGN.md §2): for every
layer k an adapter (A_k: D->R, B_k: R->D) taps the *residual-stream input*
of block k and its output is accumulated into the final hidden state:

    h_final <- y_base + sum_k x^k A_k B_k        (Eq. 17 at LM scale)

Because the backbone (including the readout table) is frozen, x^k and
y_base are constant across the fine-tuning run, so a populate epoch caches
them and every later epoch runs *zero backbone compute* — only the skip
aggregation, the readout loss, and the adapter backward.

Cache modes (``SkipLoRAConfig``):
  - ``full``      : cache x^k as-is (paper-faithful; D-wide).
  - ``int8``      : cache x^k rowwise-quantised int8 + per-token scales
                    (4x smaller than bf16-widths; beyond-paper).
  - ``freeze_a``  : freeze A_k (LoRA-FA style) and cache z^k = x^k A_k —
                    R-wide, a D/R ~ 100-1300x cache compression; only B_k
                    trains (beyond-paper).

Adapters live in a *flat* layout {"A": (L, D, R), "B": (L, R, D)} (what the
fused Pallas kernel consumes, and the per-slot layout of the serving
``AdapterPool`` — DESIGN.md §7) with converters to and from the
LayerStack's periodic layout (``adapters_to_stack`` / ``stack_to_adapters``)
for populate/serve forwards.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.skip_cache import SkipCache, cache_read, cache_write, init_cache
from repro.models.config import ModelConfig
from repro.models.lm import lm_forward, lm_loss

Params = Any


@dataclasses.dataclass(frozen=True)
class SkipLoRAConfig:
    rank: int = 16
    mode: str = "full"             # full | int8 | freeze_a
    cache_dtype: str = "bfloat16"  # dtype for unquantised slots
    use_fused_kernel: bool = False  # Pallas skip-sum (repro.kernels.skip_lora)

    def __post_init__(self):
        if self.mode not in ("full", "int8", "freeze_a"):
            raise ValueError(self.mode)


# ---------------------------------------------------------------------------
# Adapters
# ---------------------------------------------------------------------------


def init_adapters(key: jax.Array, cfg: ModelConfig, sl: SkipLoRAConfig) -> Params:
    """Flat adapters: A ~ Kaiming (fp32 master), B = 0 (identity at init)."""
    l, d, r = cfg.n_layers, cfg.d_model, sl.rank
    ka, _ = jax.random.split(key)
    return {
        "A": jax.random.normal(ka, (l, d, r), jnp.float32) / jnp.sqrt(d),
        "B": jnp.zeros((l, r, d), jnp.float32),
    }


def adapters_to_stack(adapters: Params, cfg: ModelConfig) -> Params:
    """Flat (L, ...) -> LayerStack periodic layout for stack_forward."""
    period, n_per = cfg.period, cfg.n_periods
    lp = period * n_per
    a, b = adapters["A"], adapters["B"]
    ap = a[:lp].reshape((n_per, period) + a.shape[1:])
    bp = b[:lp].reshape((n_per, period) + b.shape[1:])
    periods = [{"A": ap[:, i], "B": bp[:, i]} for i in range(period)]
    remainder = [
        {"A": a[lp + j], "B": b[lp + j]} for j in range(len(cfg.remainder_pattern))
    ]
    return {"periods": periods, "remainder": remainder}


def stack_to_adapters(stack: Params, cfg: ModelConfig) -> Params:
    """LayerStack periodic layout -> flat {"A": (L, D, R), "B": (L, R, D)}.

    Inverse of ``adapters_to_stack``; the serve-time handoff — a fine-tuned
    stack registers into an ``AdapterPool`` slot in flat layout (DESIGN.md
    §7), which is also what the grouped kernel's pool gather consumes."""
    period = cfg.period
    parts_a, parts_b = [], []
    for p in range(cfg.n_periods):
        for i in range(period):
            parts_a.append(stack["periods"][i]["A"][p])
            parts_b.append(stack["periods"][i]["B"][p])
    for rem in stack["remainder"]:
        parts_a.append(rem["A"])
        parts_b.append(rem["B"])
    return {"A": jnp.stack(parts_a), "B": jnp.stack(parts_b)}


def split_trainable(adapters: Params, sl: SkipLoRAConfig) -> tuple[Params, Params]:
    """(trainable, static). freeze_a trains only B (A folded into the cache)."""
    if sl.mode == "freeze_a":
        return {"B": adapters["B"]}, {"A": adapters["A"]}
    return adapters, {}


def merge_adapters(trainable: Params, static: Params) -> Params:
    return {**static, **trainable}


# ---------------------------------------------------------------------------
# Skip aggregation (reference path; the Pallas kernel is a drop-in)
# ---------------------------------------------------------------------------


def skip_sum_ref(acts: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """sum_k x^k A_k B_k. acts: (L,B,S,D); a: (L,D,R); b: (L,R,D) -> (B,S,D)."""
    dtype = acts.dtype
    z = jnp.einsum("lbsd,ldr->lbsr", acts, a.astype(dtype))
    return jnp.einsum("lbsr,lrd->bsd", z, b.astype(dtype))


def skip_sum(acts, a, b, *, use_kernel: bool = False) -> jax.Array:
    if use_kernel:
        from repro.kernels.skip_lora.ops import skip_lora_fused

        return skip_lora_fused(acts, a, b)
    return skip_sum_ref(acts, a, b)


def skip_sum_compressed(z: jax.Array, b: jax.Array) -> jax.Array:
    """freeze_a: z = x A cached. z: (L,B,S,R); b: (L,R,D) -> (B,S,D)."""
    return jnp.einsum("lbsr,lrd->bsd", z, b.astype(z.dtype))


# ---------------------------------------------------------------------------
# int8 rowwise quantisation (per token per layer)
# ---------------------------------------------------------------------------


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantise over the last axis. Returns (q int8, scale fp32 without last axis)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# LM Skip-Cache layout
# ---------------------------------------------------------------------------


def lm_cache_layout(
    cfg: ModelConfig, sl: SkipLoRAConfig, seq: int
) -> dict[str, tuple[tuple, Any]]:
    """slot name -> (per-sample shape, dtype)."""
    l, d, r = cfg.n_layers, cfg.d_model, sl.rank
    cdt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sl.cache_dtype]
    if sl.mode == "freeze_a":
        slots = {"z": ((l, seq, r), cdt)}
    elif sl.mode == "int8":
        slots = {"acts_q": ((l, seq, d), jnp.int8), "acts_scale": ((l, seq), jnp.float32)}
    else:
        slots = {"acts": ((l, seq, d), cdt)}
    slots["y_base"] = ((seq, d), cdt)
    slots["labels"] = ((seq,), jnp.int32)
    return slots


def init_lm_cache(
    num_samples: int, cfg: ModelConfig, sl: SkipLoRAConfig, seq: int
) -> SkipCache:
    layout = lm_cache_layout(cfg, sl, seq)
    slots = {
        name: jnp.zeros((num_samples,) + shape, dtype)
        for name, (shape, dtype) in layout.items()
    }
    return SkipCache(slots=slots, valid=jnp.zeros((num_samples,), jnp.bool_))


def cache_nbytes_per_sample(cfg: ModelConfig, sl: SkipLoRAConfig, seq: int) -> int:
    layout = lm_cache_layout(cfg, sl, seq)
    total = 0
    for shape, dtype in layout.values():
        n = 1
        for s in shape:
            n *= s
        total += n * jnp.dtype(dtype).itemsize
    return total


def _encode_acts(
    acts: jax.Array, adapters: Params, sl: SkipLoRAConfig
) -> dict[str, jax.Array]:
    """acts (L,B,S,D) -> cache slot values keyed per sample (B leading)."""
    acts_b = jnp.swapaxes(acts, 0, 1)  # (B, L, S, D)
    if sl.mode == "freeze_a":
        z = jnp.einsum("blsd,ldr->blsr", acts_b, adapters["A"].astype(acts_b.dtype))
        return {"z": z}
    if sl.mode == "int8":
        q, scale = quantize_int8(acts_b)
        return {"acts_q": q, "acts_scale": scale}
    return {"acts": acts_b}


def _decode_acts(vals: dict[str, jax.Array], sl: SkipLoRAConfig, dtype) -> jax.Array:
    """cache slots -> acts (L,B,S,D) (or z (L,B,S,R) in freeze_a mode)."""
    if sl.mode == "freeze_a":
        return jnp.swapaxes(vals["z"], 0, 1).astype(dtype)
    if sl.mode == "int8":
        acts_b = dequantize_int8(vals["acts_q"], vals["acts_scale"], dtype)
        return jnp.swapaxes(acts_b, 0, 1)
    return jnp.swapaxes(vals["acts"], 0, 1).astype(dtype)


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------


def populate_loss_fn(
    params: Params,
    cfg: ModelConfig,
    adapters: Params,
    batch: dict[str, jax.Array],
):
    """Full forward with activation collection. Returns (loss, (acts, y_base))."""
    out = lm_forward(
        params,
        cfg,
        batch["tokens"],
        mode="train",
        adapters=adapters_to_stack(adapters, cfg),
        collect_acts=True,
        prefix_embeds=batch.get("prefix_embeds"),
    )
    labels = batch["labels"]
    if batch.get("prefix_embeds") is not None:
        p = batch["prefix_embeds"].shape[1]
        pad = -jnp.ones((labels.shape[0], p), labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    loss = lm_loss(params, cfg, out["h"], labels)
    return loss, (jax.lax.stop_gradient(out["acts"]), jax.lax.stop_gradient(out["y_base"]), labels)


def make_populate_step(cfg: ModelConfig, sl: SkipLoRAConfig, optimizer):
    """jit-able: backbone fwd + cache write + adapter optimizer step."""

    def step(params, trainable, static, opt_state, cache, batch, idx):
        def loss_fn(t):
            return populate_loss_fn(params, cfg, merge_adapters(t, static), batch)

        (loss, (acts, y_base, labels)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(trainable)
        values = _encode_acts(acts, merge_adapters(trainable, static), sl)
        values["y_base"] = y_base
        values["labels"] = labels
        cache = cache_write(cache, idx, values)
        updates, opt_state = optimizer.update(grads, opt_state, trainable)
        from repro.optim.optimizers import apply_updates

        trainable = apply_updates(trainable, updates)
        return trainable, opt_state, cache, loss

    return step


def cached_loss_fn(
    params: Params,
    cfg: ModelConfig,
    sl: SkipLoRAConfig,
    adapters: Params,
    vals: dict[str, jax.Array],
    dtype,
) -> jax.Array:
    """Loss from cached activations only — zero backbone compute."""
    if sl.mode == "int8" and sl.use_fused_kernel:
        # int8 payload goes straight into the Pallas kernel: dequant is fused
        # into the A-projection, never round-tripping HBM as bf16.
        from repro.kernels.skip_lora.ops import skip_lora_fused_int8

        q = jnp.swapaxes(vals["acts_q"], 0, 1)        # (L, B, S, D)
        scale = jnp.swapaxes(vals["acts_scale"], 0, 1)  # (L, B, S)
        skip = skip_lora_fused_int8(q, scale, adapters["A"], adapters["B"])
    else:
        acts = _decode_acts(vals, sl, dtype)
        if sl.mode == "freeze_a":
            skip = skip_sum_compressed(acts, adapters["B"])
        else:
            skip = skip_sum(
                acts, adapters["A"], adapters["B"], use_kernel=sl.use_fused_kernel
            )
    h = vals["y_base"].astype(dtype) + skip.astype(dtype)
    return lm_loss(params, cfg, h, vals["labels"])


def make_cached_step_from_vals(cfg: ModelConfig, sl: SkipLoRAConfig, optimizer):
    """Adapter step from already-gathered cache values. This granularity is
    what the tiered engine's streaming read path feeds."""
    from repro.models.lm import model_dtype

    def step(params, trainable, static, opt_state, vals):
        def loss_fn(t):
            return cached_loss_fn(
                params, cfg, sl, merge_adapters(t, static), vals, model_dtype(cfg)
            )

        loss, grads = jax.value_and_grad(loss_fn)(trainable)
        updates, opt_state = optimizer.update(grads, opt_state, trainable)
        from repro.optim.optimizers import apply_updates

        trainable = apply_updates(trainable, updates)
        return trainable, opt_state, loss

    return step


def make_cached_step(cfg: ModelConfig, sl: SkipLoRAConfig, optimizer):
    """jit-able: cache gather + adapter step. This is the paper's fast path."""
    from_vals = make_cached_step_from_vals(cfg, sl, optimizer)

    def step(params, trainable, static, opt_state, cache, idx):
        return from_vals(params, trainable, static, opt_state, cache_read(cache, idx))

    return step


# ---------------------------------------------------------------------------
# Fused epoch loops: one XLA dispatch per epoch phase (DESIGN.md §2)
# ---------------------------------------------------------------------------


def make_populate_epoch(cfg: ModelConfig, sl: SkipLoRAConfig, optimizer, *,
                        donate: bool = True):
    """Whole populate epoch as one lax.scan dispatch over a pre-permuted
    batch index matrix. tokens/labels: (num_samples, seq) device arrays;
    idx_mat: (steps, batch). Carries (trainable, opt_state, cache) are
    donated so the cache updates in place across scan iterations —
    ``donate=False`` for callers that reuse the carry arrays afterwards."""
    step = make_populate_step(cfg, sl, optimizer)

    def epoch(params, trainable, static, opt_state, cache, tokens, labels, idx_mat):
        def body(carry, idx):
            t, o, c = carry
            batch = {"tokens": tokens[idx], "labels": labels[idx]}
            t, o, c, loss = step(params, t, static, o, c, batch, idx)
            return (t, o, c), loss

        (trainable, opt_state, cache), losses = jax.lax.scan(
            body, (trainable, opt_state, cache), idx_mat
        )
        return trainable, opt_state, cache, losses

    return jax.jit(epoch, donate_argnums=(1, 3, 4) if donate else ())


def make_cached_epoch(cfg: ModelConfig, sl: SkipLoRAConfig, optimizer, *,
                      donate: bool = True):
    """Whole cached epoch as one lax.scan dispatch: cache gathers + adapter
    steps only, zero backbone compute and zero Python in the loop."""
    step = make_cached_step(cfg, sl, optimizer)

    def epoch(params, trainable, static, opt_state, cache, idx_mat):
        def body(carry, idx):
            t, o = carry
            t, o, loss = step(params, t, static, o, cache, idx)
            return (t, o), loss

        (trainable, opt_state), losses = jax.lax.scan(
            body, (trainable, opt_state), idx_mat
        )
        return trainable, opt_state, losses

    return jax.jit(epoch, donate_argnums=(1, 3) if donate else ())
