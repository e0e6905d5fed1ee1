"""A dense decoder of one layer kind: multi-head or grouped attention with
partial rotary over d / heads, a gated (SiLU) or plain (tanh GELU) FFN,
LayerNorm with bias, an untied head (``stablelm-1.6b``,
``musicgen-medium``). Its plain reference is ``bench/reference/dense.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import counts
from bench.weights import NORM_STD, TABLE_STD, jax_key


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        pattern=("attn",), rope_pct=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]), ffn_activation=cfg["hidden_act"],
        ffn_gated=cfg["ffn_gated"], norm_type=cfg["norm_type"],
        norm_eps=cfg["layer_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
    )


# -- weights -----------------------------------------------------------------


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


# -- counts (``bench/counts.py`` forwards to these) -------------------------


def _d(cfg: dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"], d // h,
            cfg["intermediate_size"], cfg["vocab_size"],
            3 if cfg["ffn_gated"] else 2, int(cfg["skip_lora_rank"]))


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer."""
    L, D, H, KV, hd, F, V, nf, R = _d(cfg)
    return D * (H + 2 * KV) * hd + H * hd * D + nf * D * F


def matmul_params(cfg: dict) -> int:
    """All layers' matmul weights (no embedding gather, no head)."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg)


def attention_flops(cfg: dict, keys: int) -> float:
    """QK^T and PV for one query over ``keys`` keys, all layers."""
    L, D, H, KV, hd, F, V, nf, R = _d(cfg)
    return 4.0 * H * hd * keys * L


def decode_token_flops(cfg: dict, position: int) -> float:
    """One decode step of one row whose new token sits at ``position``
    (it attends ``position + 1`` keys), with its skip term and readout."""
    return (2.0 * matmul_params(cfg) + attention_flops(cfg, position + 1)
            + counts.skip_flops(cfg) + counts.readout_flops(cfg))


def prefill_flops(cfg: dict, length: int) -> float:
    """A causal forward over ``length`` real tokens, read out (with the
    skip term) at the last one only."""
    pairs = length * (length + 1) / 2
    return (2.0 * matmul_params(cfg) * length
            + 4.0 * cfg["num_attention_heads"] * (cfg["hidden_size"]
                                                 // cfg["num_attention_heads"])
            * pairs * cfg["num_hidden_layers"]
            + counts.skip_flops(cfg) + counts.readout_flops(cfg))
