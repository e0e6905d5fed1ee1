"""Plain float32 reference of a fine-tune job's first steps.

The program's adapt reads block inputs and the base hidden state from the
skip cache that ingest filled. This reference recomputes them with the
backbone forward of the configuration's own reference (``ref``, the
module ``bench/model.py`` finds: its ``hidden``, ``logits`` and
``cfg_items``) and then takes the same steps:

    loss_t(A, B) = mean over tenant t's rows and tokens of
                   CE(LayerNorm_f(h + sum_l x_l A_l B_l) Head^T, label)
    step:          AdamW on every tenant's (A, B) with the gradient of
                   its own loss (lr, b1 0.9, b2 0.999, eps 1e-8, no decay)

The rows each step visits follow the program's published plan: tenant t
(cache partition p) visits a permutation of its rows drawn from
``numpy.random.default_rng(((seed + p) << 10) ^ epoch)`` in batches of
``batch_per_tenant`` (``plan_rows``, copied from the repository's planner).
The initial adapters follow the repository's recipe from the adapt key:
per tenant ``A = N(0, 1) / sqrt(D)`` from the first half of its split key,
``B = 0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import skip_term

B1, B2, EPS = 0.9, 0.999, 1e-8


def plan_rows(seed: int, partitions, epoch: int, n: int, bpt: int) -> np.ndarray:
    """(steps, tenants, bpt) row ids within each tenant's own samples."""
    cols = []
    for p in partitions:
        perm = np.random.default_rng(((seed + p) << 10) ^ epoch).permutation(n)
        steps = -(-n // bpt)
        pad = steps * bpt - n
        ids = np.concatenate([perm, perm[:pad]]) if pad else perm
        cols.append(ids.reshape(steps, bpt))
    return np.stack(cols, axis=1)


def init_adapters(key, n: int, L: int, D: int, R: int) -> dict:
    keys = jax.random.split(key, n)

    def one(k):
        ka, _ = jax.random.split(k)
        return jax.random.normal(ka, (L, D, R), jnp.float32) / jnp.sqrt(D)

    a = jax.vmap(one)(keys)
    return {"A": a, "B": jnp.zeros((n, L, R, D), jnp.float32)}


@functools.partial(jax.jit, static_argnames=("ref", "cfg_items", "precision"))
def _features(params, tokens, *, ref, cfg_items, precision):
    """Per sample: (final hidden (S, D), block inputs (L, S, D))."""
    cfg = dict(cfg_items)
    return jax.lax.map(
        lambda t: ref.hidden(params, cfg, t, precision), tokens, batch_size=8
    )


def features(ref, params, cfg: dict, tokens, precision: str = "f32"):
    """(h (N, S, D), xs (N, L, S, D)) for N samples of S tokens."""
    return _features(
        params, jnp.asarray(tokens), ref=ref, cfg_items=ref.cfg_items(cfg),
        precision=precision,
    )


def _tenant_loss(ref, params, cfg, precision, a, b, h, xs, labels):
    """Mean CE of one tenant's rows: h (n, S, D), xs (n, L, S, D)."""
    skip = jax.vmap(lambda x: skip_term(x, a, b, precision))(xs)
    lg = ref.logits(params, cfg, h + skip, precision)
    lp = jax.nn.log_softmax(lg, -1)
    ll = jnp.take_along_axis(lp, labels[..., None], -1)[..., 0]
    return -jnp.mean(ll)


@functools.partial(jax.jit, static_argnames=("ref", "cfg_items", "precision"))
def _step(params, ad, mu, nu, count, h, xs, labels, lr, *, ref, cfg_items,
          precision):
    """One AdamW step for every tenant: h (T, n, S, D), xs (T, n, L, S, D),
    labels (T, n, S). Returns (adapters, mu, nu, losses (T,), grads)."""
    cfg = dict(cfg_items)
    loss = functools.partial(_tenant_loss, ref, params, cfg, precision)

    def per_tenant(a, b, ht, xt, lt):
        return jax.value_and_grad(loss, argnums=(0, 1))(a, b, ht, xt, lt)

    losses, (ga, gb) = jax.vmap(per_tenant)(ad["A"], ad["B"], h, xs, labels)
    g = {"A": ga, "B": gb}
    mu = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
    c = count + 1.0
    upd = jax.tree.map(
        lambda m, v: -lr * (m / (1 - B1**c)) / (jnp.sqrt(v / (1 - B2**c)) + EPS),
        mu, nu,
    )
    ad = jax.tree.map(lambda p, u: p + u, ad, upd)
    return ad, mu, nu, losses, g


def run_steps(ref, params, cfg: dict, h, xs, labels, plan, ad0, lr: float,
              n_steps: int, precision: str = "f32"):
    """``n_steps`` steps from ``ad0``. h (T, n, S, D), xs (T, n, L, S, D),
    labels (T, n, S) per tenant; ``plan`` (steps, T, bpt) row ids.
    Returns a list, per step, of {"losses", "adapters", "mu", "nu",
    "grads"} (adapters and moments after that step)."""
    zeros = jax.tree.map(jnp.zeros_like, ad0)
    ad, mu, nu = ad0, zeros, zeros
    out = []
    rows = jnp.arange(plan.shape[1])[:, None]
    for s in range(n_steps):
        idx = jnp.asarray(plan[s])
        ad, mu, nu, losses, g = _step(
            params, ad, mu, nu, jnp.float32(s), h[rows, idx], xs[rows, idx],
            labels[rows, idx], jnp.float32(lr), ref=ref,
            cfg_items=ref.cfg_items(cfg), precision=precision,
        )
        out.append({"losses": losses, "adapters": ad, "mu": mu, "nu": nu,
                    "grads": g})
    return out
