"""Weights and adapters from the seed, made on the device in one jitted call
each, in the layout the program serves (stacked over layers) and in the
configuration's dtype. The plain reference reads the same arrays: they are
the benchmark's, not the program's."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORM_STD = 0.1      # LayerNorm scale 1 + N(0, NORM_STD^2), bias N(0, NORM_STD^2)
TABLE_STD = 0.02    # embedding and head tables


def jax_key(seed: int, *tags: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32 bits are
    folded in separately) and further integer tags."""
    key = jax.random.fold_in(jax.random.key(0), seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for t in tags:
        key = jax.random.fold_in(key, t)
    return key


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "D": d, "H": h,
        "KV": cfg["num_key_value_heads"], "hd": d // h,
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "gated": bool(cfg["ffn_gated"]),
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_params(key, shape_items, dtype_name):
    n = dict(shape_items)
    dtype = jnp.dtype(dtype_name)
    L, D, H, KV, hd, F, V = (n[k] for k in ("L", "D", "H", "KV", "hd", "F", "V"))
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dtype)

    def norm(shape):
        return {
            "scale": 1.0 + NORM_STD * jax.random.normal(next(ks), shape, jnp.float32),
            "bias": NORM_STD * jax.random.normal(next(ks), shape, jnp.float32),
        }

    layer = {
        "norm1": norm((L, D)),
        "attn": {
            "wq": normal((L, D, H, hd), D ** -0.5),
            "wk": normal((L, D, KV, hd), D ** -0.5),
            "wv": normal((L, D, KV, hd), D ** -0.5),
            "wo": normal((L, H, hd, D), (H * hd) ** -0.5),
        },
        "norm2": norm((L, D)),
        "ffn": {
            "w_up": normal((L, D, F), D ** -0.5),
            "w_down": normal((L, F, D), F ** -0.5),
        },
    }
    if n["gated"]:
        layer["ffn"]["w_gate"] = normal((L, D, F), D ** -0.5)
    return {
        "embed": {"table": normal((V, D), TABLE_STD)},
        "stack": {"periods": [layer], "remainder": []},
        "final_norm": norm((D,)),
        "head": {"table": normal((V, D), TABLE_STD)},
    }


def make_params(cfg: dict, seed: int):
    """Backbone weights in the program's layout: one attention pattern
    position stacked over all layers (``periods[0]``), untied head."""
    if cfg["tie_word_embeddings"]:
        raise ValueError("tied embeddings are not among the benchmark's configs")
    return _make_params(
        jax_key(seed, 1), tuple(sorted(dims(cfg).items())), cfg["torch_dtype"]
    )


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make_adapters(key, n, L, D, R):
    ka, kb = jax.random.split(key)
    return {
        "A": jax.random.normal(ka, (n, L, D, R), jnp.float32) * D ** -0.5,
        "B": jax.random.normal(kb, (n, L, R, D), jnp.float32) * (R * L) ** -0.5,
    }


def make_adapters(cfg: dict, seed: int, n: int):
    """n served tenants' adapters {"A": (n, L, D, R), "B": (n, L, R, D)}
    in float32, as if trained: B is not zero, so each tenant's skip term
    moves its logits."""
    d = dims(cfg)
    return _make_adapters(
        jax_key(seed, 2), n, d["L"], d["D"], int(cfg["skip_lora_rank"])
    )
