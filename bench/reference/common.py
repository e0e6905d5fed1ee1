"""What every plain decoder reference shares: float32 matmuls at
``highest`` precision, the float8 control's rounding, rotary positions
and the skip-LoRA term. Imports nothing of the program.

``precision="fp8"`` is the control: every weight matmul's operands are
rounded to float8 e4m3 (scaled per output channel and per token to the
format's range) before the product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FP8_MAX = 448.0


def fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """x (..., K) @ w (K, N) in float32; fp8 rounds both operands."""
    if precision == "fp8":
        x = fp8(x, -1)
        w = fp8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


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


def skip_term(xs: jax.Array, a: jax.Array, b: jax.Array, precision: str = "f32"):
    """sum_l x_l A_l B_l: xs (L, T, D), a (L, D, R), b (L, R, D) -> (T, D)."""
    if precision == "fp8":
        xs, a, b = fp8(xs, -1), fp8(a, 1), fp8(b, 1)
    z = jnp.einsum("ltd,ldr->ltr", xs, a, precision="highest")
    return jnp.einsum("ltr,lrd->td", z, b, precision="highest")
