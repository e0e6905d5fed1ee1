"""Plain float32 reference of the dense decoder (``bench/models/dense.py``).

Written from the layer equations, one sequence at a time, with nothing
imported from the program. Per layer l (pre-norm, residual):

    x_l   = h                                        (the skip tap)
    a     = LayerNorm_1(h)
    q,k,v = a Wq, a Wk, a Wv        per head, rotary on the first
                                    partial_rotary_factor of each head's
                                    dims (two halves rotated, as NeoX)
    h     = h + softmax(q k^T / sqrt(hd), causal) v Wo
    f     = LayerNorm_2(h)
    h     = h + (act(f Wg) * (f Wu)) Wd     gated (SiLU)
          | act(f Wu) Wd                    ungated (tanh GELU)

    logits = LayerNorm_f(h_L + sum_l x_l A_l B_l) Head^T

Weights are the benchmark's (``bench/models/dense.py``), stacked over
layers; each layer is cast to float32 inside the layer scan. Matmuls run
at ``highest`` precision; ``precision="fp8"`` is the control
(``bench/reference/common.py``).

Departures from the published models are the configuration's ``assumed``
list; this file follows the repository's equations where they differ.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.common import mm, rotary, skip_term


def layer_norm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _layer(cfg: dict, precision: str, h: jax.Array, w: dict):
    """One decoder layer over (T, D) in float32; returns (h, block input)."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    t, d = h.shape
    eps = cfg["layer_norm_eps"]
    a = layer_norm(w["norm1"], h, eps)
    at = w["attn"]
    n_h, hd = at["wq"].shape[1], at["wq"].shape[2]
    n_kv = at["wk"].shape[1]
    q = mm(a, at["wq"].reshape(d, -1), precision).reshape(t, n_h, hd)
    k = mm(a, at["wk"].reshape(d, -1), precision).reshape(t, n_kv, hd)
    v = mm(a, at["wv"].reshape(d, -1), precision).reshape(t, n_kv, hd)
    pos = jnp.arange(t)
    frac, theta = cfg["partial_rotary_factor"], cfg["rope_theta"]
    q, k = rotary(q, pos, frac, theta), rotary(k, pos, frac, theta)
    rep = n_h // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / jnp.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision="highest")
    x_in = h
    h = h + mm(o.reshape(t, -1), at["wo"].reshape(-1, d), precision)
    f = layer_norm(w["norm2"], h, eps)
    ff = w["ffn"]
    if cfg["ffn_gated"]:
        g = mm(f, ff["w_gate"], precision)
        u = jax.nn.silu(g) * mm(f, ff["w_up"], precision)
    else:
        u = gelu_tanh(mm(f, ff["w_up"], precision))
    h = h + mm(u, ff["w_down"], precision)
    return h, x_in


def hidden(params: dict, cfg: dict, tokens: jax.Array, precision: str = "f32"):
    """(final hidden (T, D), block inputs (L, T, D)) of one sequence."""
    h = params["embed"]["table"][tokens].astype(jnp.float32)
    body = functools.partial(_layer, cfg, precision)
    h, xs = jax.lax.scan(body, h, params["stack"]["periods"][0])
    return h, xs


def logits(params: dict, cfg: dict, y: jax.Array, precision: str = "f32"):
    """LayerNorm_f(y) Head^T: (..., D) -> (..., V) float32."""
    yn = layer_norm(params["final_norm"], y, cfg["layer_norm_eps"])
    head = params["head"]["table"].astype(jnp.float32)
    return mm(yn, head.T, precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _sequence_logits(params, tokens, a, b, *, cfg_items, precision):
    cfg = dict(cfg_items)
    h, xs = hidden(params, cfg, tokens, precision)
    y = h + skip_term(xs, a, b, precision)
    return logits(params, cfg, y, precision)


def cfg_items(cfg: dict) -> tuple:
    """The numeric part of a configuration file, hashable for ``jit``."""
    keep = ("layer_norm_eps", "partial_rotary_factor", "rope_theta", "ffn_gated")
    return tuple((k, cfg[k]) for k in keep)


def sequence_logits(params, cfg: dict, tokens, a, b, precision: str = "f32"):
    """Logits (T, V) of one token sequence under adapters a (L, D, R) and
    b (L, R, D) (zeros for the base model)."""
    return _sequence_logits(
        params, tokens, a, b, cfg_items=cfg_items(cfg), precision=precision
    )
