"""The decode layer scan carries the stacked KV caches and writes in place.

``stack_forward`` in decode mode keeps each attention position's stacked
(n_periods, B, S, nk, hd) K/V in the layer scan's carry: every layer writes
its one new token at ``(layer, row, pos)`` and reads its slice straight
from the carry, while recurrent states still ride the scan as xs/ys. That
is a change of data movement, not of math, so the bar is bitwise equality
with the per-layer form — each layer's cache sliced out, decoded by
``block_forward`` and stacked back — over several steps, for a scalar and a
per-row position: the logits, every written K/V and every recurrent state.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, reduce_config
from repro.models import blocks as B
from repro.models.lm import init_lm, init_serve_caches, readout

BATCH, MAX_SEQ, STEPS = 4, 16, 3

#: pure attention; sliding-window local + global with a remainder; a
#: hybrid whose mamba states are rewritten whole each step.
ARCHS = ["stablelm-1.6b", "gemma3-27b", "jamba-1.5-large-398b"]


def per_layer_forward(stack, h, cfg, caches, pos):
    """Decode every layer against its own (B, S, ...) cache slice."""
    periods = [[] for _ in cfg.pattern]
    for layer in range(cfg.n_periods):
        for i, kind in enumerate(cfg.pattern):
            p = jax.tree.map(lambda x: x[layer], stack["periods"][i])
            c = jax.tree.map(lambda x: x[layer], caches["periods"][i])
            h, c, _ = B.block_forward(kind, p, h, cfg, mode="decode",
                                      cache=c, pos=pos)
            periods[i].append(c)
    rem = []
    for j, kind in enumerate(cfg.remainder_pattern):
        h, c, _ = B.block_forward(kind, stack["remainder"][j], h, cfg,
                                  mode="decode", cache=caches["remainder"][j],
                                  pos=pos)
        rem.append(c)
    stacked = [jax.tree.map(lambda *xs: jnp.stack(xs), *cs) for cs in periods]
    return h, {"periods": stacked, "remainder": rem}


def carried_forward(stack, h, cfg, caches, pos):
    out = B.stack_forward(stack, h, cfg, mode="decode", caches=caches, pos=pos)
    return out["h"], out["caches"]


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("vector", [False, True], ids=["scalar", "vector"])
def test_carried_cache_decode_matches_per_layer(arch, vector):
    cfg = reduce_config(get_config(arch))
    assert any(k in B.ATTN_KINDS for k in cfg.pattern)
    params = init_lm(jax.random.key(0), cfg)
    caches = init_serve_caches(cfg, BATCH, MAX_SEQ)
    leaves, tdef = jax.tree.flatten(caches)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    caches = jax.tree.unflatten(tdef, [
        jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
        for k, x in zip(keys, leaves)
    ])
    pos0 = (jnp.asarray([2, 9, 0, 12], jnp.int32) if vector
            else jnp.asarray(9, jnp.int32))

    def run(forward):
        fwd = jax.jit(lambda st, h, c, p: forward(st, h, cfg, c, p))
        c, outs = caches, []
        for step in range(STEPS):
            h = jax.random.normal(jax.random.key(10 + step),
                                  (BATCH, 1, cfg.d_model), jnp.float32)
            h, c = fwd(params["stack"], h, c, pos0 + step)
            outs.append((readout(params, cfg, h), c))
        return outs

    for (lg, c), (lg_ref, c_ref) in zip(run(carried_forward),
                                        run(per_layer_forward)):
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg_ref))
        assert jax.tree.structure(c) == jax.tree.structure(c_ref)
        for x, y in zip(jax.tree.leaves(c), jax.tree.leaves(c_ref)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
