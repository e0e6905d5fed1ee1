"""GPipe-style pipeline parallelism for the session serve path.

Two consumers:

  - ``pipeline_apply``: generic pipelined layer stack (used by the schedule
    tests and as the reference for the math below).
  - ``pipeline_prefill``: the session serve-prefill body — each stage holds a
    contiguous block of backbone layers *and* the adapter-pool rows for those
    layers, computes its blocks' skip-LoRA terms from locally-available block
    inputs (the paper's skip connections read block inputs only, so the
    adapter reduction composes across stages), and forwards ``(h, skip)`` to
    the next stage over ``lax.ppermute``. ``SessionRuntime(pipeline_stages=N)``
    wires this in as the alternative partitioning of the 2-D session mesh:
    the same ``model``-axis device group that otherwise TP-shards the
    backbone is repurposed as N pipeline stages.

Implementation: ``shard_map`` over the pipeline axis; each device holds one
stage of layers (params stacked on a leading stage axis, sharded over the
axis). The classic GPipe schedule runs ``n_micro + n_stages - 1`` ticks; at
each tick a stage processes one microbatch and hands its activation to the
next stage via ``lax.ppermute``. Bubble fraction = (P-1)/(M+P-1) — the
request scheduler sizes microbatches from its ``_LiveBatch`` admissions so
continuous batching keeps the realized bubble near this prediction.
``pipeline_apply`` is fully differentiable (ppermute transposes to the
reverse permutation), so ``jax.grad`` through it yields pipelined backward
for free.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.runtime.sharding import suspend_scope

Params = Any


def split_stages(
    layer_params: list[Params], n_stages: int
) -> tuple[Params, jax.Array]:
    """Group per-layer params into ``n_stages`` stacked stage pytrees.

    ``layer_params`` is a list of identically-structured per-layer pytrees,
    length L. Returns ``(stages, valid)``: ``stages`` has leading dims
    ``(n_stages, ceil(L / n_stages), ...)`` ready to shard over the pipeline
    axis; when ``L % n_stages != 0`` the last stage is padded with copies of
    the final layer and ``valid`` (bool, ``(n_stages, ceil(L/n_stages))``)
    marks the pads False so pipeline runners pass activations through them
    unchanged.
    """
    l = len(layer_params)
    if l == 0 or n_stages <= 0:
        raise ValueError(f"need >=1 layer and >=1 stage, got {l}/{n_stages}")
    if n_stages > l:
        raise ValueError(f"{n_stages} stages for {l} layers leaves empty stages")
    per = -(-l // n_stages)
    padded = list(layer_params) + [layer_params[-1]] * (n_stages * per - l)
    if len({jax.tree.structure(p) for p in padded}) != 1:
        raise ValueError(
            "split_stages needs identically-structured layers "
            "(uniform block stacks only)"
        )
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *padded)
    stages = jax.tree.map(
        lambda x: x.reshape((n_stages, per) + x.shape[1:]), stacked
    )
    valid = jnp.asarray(np.arange(n_stages * per).reshape(n_stages, per) < l)
    return stages, valid


def pipeline_apply(
    stage_params: Params,
    x_micro: jax.Array,
    layer_fn: Callable[[Params, jax.Array], jax.Array],
    *,
    mesh: Mesh,
    axis: str = "pod",
    valid: jax.Array = None,
) -> jax.Array:
    """Run the pipelined stack over microbatches.

    stage_params: (n_stages, layers_per_stage, ...) pytree, sharded on the
        leading axis over ``axis``.
    x_micro: (n_micro, micro_batch, ...) activations (replicated).
    valid: optional (n_stages, layers_per_stage) bool from ``split_stages``;
        False layers pass activations through unchanged.
    Returns (n_micro, micro_batch, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    lead = jax.tree.leaves(stage_params)[0].shape
    if lead[0] != n_stages:
        raise ValueError(
            f"stage_params leading dim {lead[0]} != mesh axis {axis}={n_stages}"
        )
    if valid is None:
        valid = jnp.ones((n_stages, lead[1]), bool)

    def stage_block(params_block, valid_block, x):
        # params_block: (1, layers_per_stage, ...) — this device's stage.
        def body(h, xs):
            layer_p, v = xs
            return jnp.where(v, layer_fn(layer_p, h), h), None

        h, _ = jax.lax.scan(
            body, x, (jax.tree.map(lambda a: a[0], params_block), valid_block[0])
        )
        return h

    def per_stage(params_block, valid_block, x_all):
        stage_id = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(x_all[0])          # incoming activation
        outs = jnp.zeros_like(x_all)            # collected at the last stage

        def tick(carry, t):
            buf, outs = carry
            # Stage 0 ingests microbatch t (if still in range).
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jax.lax.dynamic_index_in_dim(x_all, mb_idx, keepdims=False)
            h_in = jnp.where(stage_id == 0, x_in, buf)
            h_out = stage_block(params_block, valid_block, h_in)
            # Pass to the next stage (ring; last stage's send wraps to 0 and
            # is ignored there).
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(h_out, axis, perm)
            # Last stage: microbatch t' = t - (n_stages - 1) finished at tick t.
            done_idx = t - (n_stages - 1)
            ok = jnp.logical_and(done_idx >= 0, stage_id == n_stages - 1)
            safe_idx = jnp.clip(done_idx, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, safe_idx, keepdims=False)
            upd = jnp.where(ok, h_out, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, safe_idx, 0)
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # Broadcast the last stage's collected outputs to every stage.
        outs = jax.lax.ppermute(
            outs, axis, [( (n_stages - 1 + i) % n_stages, i) for i in range(n_stages)]
        ) if n_stages > 1 else outs
        # After the permute above, stage 0 holds the result; share it around.
        outs = jax.lax.all_gather(outs, axis)[0] if n_stages > 1 else outs
        return outs

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(spec_params, P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    # The stage body is manual SPMD over ``axis``: any ambient ShardScope's
    # auto-constraints would name an axis shard_map has claimed as manual.
    with suspend_scope():
        return fn(stage_params, valid, x_micro)


def pipeline_prefill(
    stage_blocks: Params,
    stage_a: jax.Array,
    stage_b: jax.Array,
    valid: jax.Array,
    x_micro: jax.Array,
    lens: jax.Array,
    slots: jax.Array,
    block_fn: Callable[[Params, jax.Array], tuple[jax.Array, Params]],
    *,
    mesh: Mesh,
    axis: str = "model",
):
    """Pipelined serve prefill with per-stage skip-LoRA accumulation.

    stage_blocks: block params, leaves (n_stages, Lp, ...), sharded P(axis).
    stage_a / stage_b: adapter pools restacked per stage layer —
        (n_stages, Lp, n_slots, D, R) / (n_stages, Lp, n_slots, R, D),
        sharded P(axis) so each stage holds only its resident layers' rows.
    valid: (n_stages, Lp) bool from ``split_stages`` (pads contribute no
        block transform and no skip term).
    x_micro: (n_micro, mb, T, D) embedded prompt activations (replicated).
    lens: (n_micro, mb) int32 per-row prompt lengths (replicated).
    slots: (n_micro, mb) int32 per-row adapter slot (replicated).
    block_fn: (layer_params, h) -> (h_out, kv_cache) one block, prefill mode.

    The traveling carry is ``(h, skip)``: each stage reads its blocks'
    *inputs* at every row's last real position (``max(len,1)-1`` — the same
    padding semantics as ``lm.sched_prefill``), adds
    ``(h_l @ A[slot, l]) @ B[slot, l]`` for its resident layers, and the
    last stage emits the completed sum — the single-stitch reduction the
    skip-architecture admits because no term reads another layer's output.

    Returns ``(y, skip, caches)``: final hiddens (n_micro, mb, T, D) and
    skip sums (n_micro, mb, D), both replicated; kv caches with leaves
    (n_stages, Lp, n_micro, mb, ...) sharded P(axis) in stage-major flat
    layer order (pads at the tail).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    if jax.tree.leaves(stage_blocks)[0].shape[0] != n_stages:
        raise ValueError(
            f"stage_blocks leading dim != mesh axis {axis}={n_stages}"
        )

    def per_stage(blocks, a_pool, b_pool, vld, x_all, lens_all, slot_all):
        stage_id = jax.lax.axis_index(axis)
        blocks0 = jax.tree.map(lambda v: v[0], blocks)
        a0, b0, v0 = a_pool[0], b_pool[0], vld[0]
        buf_h = jnp.zeros_like(x_all[0])
        buf_skip = jnp.zeros(x_all.shape[1:2] + x_all.shape[3:], x_all.dtype)

        def tick(carry, t):
            buf_h, buf_skip = carry
            m_in = jnp.clip(t, 0, n_micro - 1)
            # Microbatch m reaches stage s at tick m + s.
            m_my = jnp.clip(t - stage_id, 0, n_micro - 1)
            h = jnp.where(
                stage_id == 0,
                jax.lax.dynamic_index_in_dim(x_all, m_in, keepdims=False),
                buf_h,
            )
            skip = jnp.where(stage_id == 0, jnp.zeros_like(buf_skip), buf_skip)
            row_len = jnp.take(lens_all, m_my, axis=0)
            row_slot = jnp.take(slot_all, m_my, axis=0)
            last = (jnp.maximum(row_len, 1) - 1).astype(jnp.int32)

            def layer(carry, xs):
                h, skip = carry
                p_l, a_l, b_l, v_l = xs
                # Skip term from the block INPUT at the last real position.
                hl = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
                a_rows = jnp.take(a_l, row_slot, axis=0).astype(h.dtype)
                b_rows = jnp.take(b_l, row_slot, axis=0).astype(h.dtype)
                term = jnp.einsum("md,mdr->mr", hl, a_rows)
                term = jnp.einsum("mr,mrd->md", term, b_rows)
                h2, cache = block_fn(p_l, h)
                return (
                    jnp.where(v_l, h2, h),
                    jnp.where(v_l, skip + term, skip),
                ), cache

            (h, skip), caches_t = jax.lax.scan(
                layer, (h, skip), (blocks0, a0, b0, v0)
            )
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt_h = jax.lax.ppermute(h, axis, perm)
            nxt_skip = jax.lax.ppermute(skip, axis, perm)
            return (nxt_h, nxt_skip), (h, skip, caches_t)

        _, (ys_h, ys_skip, ys_caches) = jax.lax.scan(
            tick, (buf_h, buf_skip), jnp.arange(ticks)
        )
        # This stage processed microbatch m at tick m + stage_id: gather the
        # per-tick cache stack back into microbatch order, (Lp, n_micro, ...).
        my_ticks = jnp.arange(n_micro) + stage_id
        caches = jax.tree.map(
            lambda c: jnp.swapaxes(jnp.take(c, my_ticks, axis=0), 0, 1)[None],
            ys_caches,
        )
        # The last stage finished microbatch m at tick m + n_stages - 1.
        done = jnp.arange(n_micro) + (n_stages - 1)
        y = jnp.take(ys_h, done, axis=0)
        sk = jnp.take(ys_skip, done, axis=0)
        if n_stages > 1:
            shift = [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)]
            y = jax.lax.all_gather(jax.lax.ppermute(y, axis, shift), axis)[0]
            sk = jax.lax.all_gather(jax.lax.ppermute(sk, axis, shift), axis)[0]
        return y, sk, caches

    spec_blocks = jax.tree.map(lambda _: P(axis), stage_blocks)
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(spec_blocks, P(axis), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(), P(), P(axis)),   # specs broadcast over output pytrees
        check_vma=False,
    )
    # Manual SPMD region: suspend any ambient ShardScope so the blocks'
    # auto-constraints (which name this same axis) don't trace inside it.
    with suspend_scope():
        return fn(stage_blocks, stage_a, stage_b, valid, x_micro, lens, slots)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
