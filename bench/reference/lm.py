"""Plain float32 reference of the decoder the benchmark serves and tunes.

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

Weights are the benchmark's (``bench/weights.py``), stacked over layers;
each layer is cast to float32 inside the layer scan. Matmuls run at
``highest`` precision. ``precision="fp8"`` is the control: every weight
matmul's operands are rounded to float8 e4m3 (scaled per output channel
and per token to the format's range) before the product.

Departures from the published models are the configuration's ``assumed``
list; this file follows the repository's equations where they differ.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

FP8_MAX = 448.0


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """x (..., K) @ w (K, N) in float32; fp8 rounds both operands."""
    if precision == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def layer_norm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rotary(x: jax.Array, pos: jax.Array, frac: float, theta: float) -> jax.Array:
    """x (T, H, hd); rotate the first ``frac`` of hd as two halves."""
    hd = x.shape[-1]
    rot = int(hd * frac) // 2 * 2
    if rot == 0:
        return x
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, rot/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2 : rot]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out, x[..., rot:]], -1)


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
    q = _mm(a, at["wq"].reshape(d, -1), precision).reshape(t, n_h, hd)
    k = _mm(a, at["wk"].reshape(d, -1), precision).reshape(t, n_kv, hd)
    v = _mm(a, at["wv"].reshape(d, -1), precision).reshape(t, n_kv, hd)
    pos = jnp.arange(t)
    frac, theta = cfg["partial_rotary_factor"], cfg["rope_theta"]
    q, k = rotary(q, pos, frac, theta), rotary(k, pos, frac, theta)
    rep = n_h // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / jnp.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision="highest")
    x_in = h
    h = h + _mm(o.reshape(t, -1), at["wo"].reshape(-1, d), precision)
    f = layer_norm(w["norm2"], h, eps)
    ff = w["ffn"]
    if cfg["ffn_gated"]:
        g = _mm(f, ff["w_gate"], precision)
        u = jax.nn.silu(g) * _mm(f, ff["w_up"], precision)
    else:
        u = gelu_tanh(_mm(f, ff["w_up"], precision))
    h = h + _mm(u, ff["w_down"], precision)
    return h, x_in


def hidden(params: dict, cfg: dict, tokens: jax.Array, precision: str = "f32"):
    """(final hidden (T, D), block inputs (L, T, D)) of one sequence."""
    h = params["embed"]["table"][tokens].astype(jnp.float32)
    body = functools.partial(_layer, cfg, precision)
    h, xs = jax.lax.scan(body, h, params["stack"]["periods"][0])
    return h, xs


def skip_term(xs: jax.Array, a: jax.Array, b: jax.Array, precision: str = "f32"):
    """sum_l x_l A_l B_l: xs (L, T, D), a (L, D, R), b (L, R, D) -> (T, D)."""
    if precision == "fp8":
        xs, a, b = _fp8(xs, -1), _fp8(a, 1), _fp8(b, 1)
    z = jnp.einsum("ltd,ldr->ltr", xs, a, precision="highest")
    return jnp.einsum("ltr,lrd->td", z, b, precision="highest")


def logits(params: dict, cfg: dict, y: jax.Array, precision: str = "f32"):
    """LayerNorm_f(y) Head^T: (..., D) -> (..., V) float32."""
    yn = layer_norm(params["final_norm"], y, cfg["layer_norm_eps"])
    head = params["head"]["table"].astype(jnp.float32)
    return _mm(yn, head.T, precision)


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
