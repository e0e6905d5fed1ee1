"""What every model's weights share: the key from a seed, the random
recipe's scales, and the served adapters. A model's own weights are its
module's ``make_params`` (``bench/model.py``), made on the device in one
jitted call, in the layout the program serves (stacked over layers) and
in the configuration's dtype. The plain reference reads the same arrays:
they are the benchmark's, not the program's."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORM_STD = 0.1      # norm scale 1 + N(0, NORM_STD^2), LayerNorm bias N(0, NORM_STD^2)
TABLE_STD = 0.02    # embedding and head tables


def jax_key(seed: int, *tags: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32 bits are
    folded in separately) and further integer tags."""
    key = jax.random.fold_in(jax.random.key(0), seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for t in tags:
        key = jax.random.fold_in(key, t)
    return key


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
    return _make_adapters(
        jax_key(seed, 2), n, cfg["num_hidden_layers"], cfg["hidden_size"],
        int(cfg["skip_lora_rank"]),
    )
