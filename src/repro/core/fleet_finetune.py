"""Fleet fine-tuning: N tenants' Skip2-LoRA adapters trained in ONE dispatch.

Skip2-LoRA's premise is a fleet of devices each fine-tuning its own adapter
stack against a shared frozen backbone. The server-side mirror of that story
(DESIGN.md §8) is *grouped* training: instead of N ``finetune()`` calls —
N scan dispatches per epoch, N optimizer states marched separately — one
``lax.scan``-stepped loop advances every tenant at once:

  - **Fleet batch**: each step concatenates one batch per tenant
    (``batch_per_tenant`` rows each, tenant-contiguous), so the row->slot
    map is the static ``repeat(arange(N), bpt)``.
  - **Grouped VJP**: the skip-sum over the whole fleet batch is one
    ``skip_lora_grouped_train`` call (trainable custom VJP over the stacked
    pool); its backward lands per-tenant ``dA[t]/dB[t]`` blocks directly
    into the stacked gradient — no per-tenant loop anywhere.
  - **Per-tenant losses**: ``lm_loss_rows`` exposes per-row log-likelihood
    sums; reducing per contiguous tenant group makes tenant t's loss (and
    hence its gradient) *identical* to training t alone — the fleet sum of
    per-tenant means decouples, so ``n_tenants=1`` reproduces the
    single-tenant trajectory step for step.
  - **Stacked optimizer states**: elementwise optimizers (SGD/Adam) over
    the stacked ``(N, ...)`` pytree are exactly N independent optimizers
    (shared step counter; no cross-element coupling).
  - **Cache partitions**: tenant t owns sample ids ``[t*n_per, (t+1)*n_per)``
    of one ``SkipCache`` / ``TieredCacheEngine`` — an id convention, which is
    why the populate epoch shares a single backbone dispatch per fleet batch
    and cached epochs gather all tenants' rows in one read (the trainer
    addresses globally-offset ids directly; ``cache_engine.TenantView`` is
    the per-tenant accessor for callers that stream one tenant's data).
  - **Write-back**: trained slots install into a serving ``AdapterPool``
    via one batched donated write (``AdapterPool.register_many``).

The tenant axis is embarrassingly parallel (the backbone is frozen and
replicated), which is what the mesh-native ``SessionRuntime`` exploits:
tenants place onto logical shards and every (trajectory, shard) group's
cached epochs dispatch on that shard's device (DESIGN.md §10) — the one
multi-device fine-tuning path since the bespoke ``shard_map`` launcher
collapsed into it.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lm_skiplora as SL
from repro.core.skip_cache import SkipCache, cache_read, cache_write
from repro.kernels.skip_lora.ops import (
    skip_lora_grouped_train,
    skip_lora_grouped_train_int8,
)
from repro.models.config import ModelConfig
from repro.models.lm import lm_forward, lm_loss_rows, model_dtype
from repro.optim.optimizers import adamw, apply_updates
from repro.runtime.sharding import constrain

Params = Any


# ---------------------------------------------------------------------------
# Stacked adapters and fleet batches
# ---------------------------------------------------------------------------


def init_fleet_adapters(
    key: jax.Array, cfg: ModelConfig, sl: SL.SkipLoRAConfig, n_tenants: int
) -> Params:
    """Stacked per-tenant adapters {"A": (N, L, D, R), "B": (N, L, R, D)},
    each tenant initialised as an independent ``init_adapters`` draw."""
    keys = jax.random.split(key, n_tenants)
    return jax.vmap(lambda k: SL.init_adapters(k, cfg, sl))(keys)


def tenant_adapters(stacked: Params, t: int) -> Params:
    """Slice tenant t's flat {"A": (L, D, R), "B": (L, R, D)} stack."""
    return jax.tree.map(lambda x: x[t], stacked)


def stack_tenant_adapters(adapters: list[Params]) -> Params:
    """Inverse of ``tenant_adapters`` over a full fleet."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *adapters)


def fleet_row_tenant(n_tenants: int, batch_per_tenant: int) -> jax.Array:
    """(N * bpt,) int32 row->tenant map of a tenant-contiguous fleet batch."""
    return jnp.repeat(jnp.arange(n_tenants, dtype=jnp.int32), batch_per_tenant)


def fleet_index_matrix(
    epoch: int,
    n_tenants: int,
    samples_per_tenant: int,
    batch_per_tenant: int,
    *,
    seed: int = 0,
) -> np.ndarray:
    """(steps, N * bpt) global sample ids: column block t is tenant t's
    pre-permuted epoch visitation (its own RNG stream, so tenant t sees the
    same order it would training alone), offset into its cache partition.

    Thin wrapper over the shared planner (``core.batch_plan``) with the
    offline convention: fleet position t owns cache partition t. Covers ALL
    samples_per_tenant rows via ``tail="wrap"`` — dropping the remainder
    would leave rows unpopulated in epoch 0 that a later epoch's different
    permutation would then read as garbage (or a KeyError on the engine
    path)."""
    from repro.core import batch_plan

    return batch_plan.fleet_index_matrix(
        epoch, n_tenants, samples_per_tenant, batch_per_tenant, seed=seed
    )


def per_tenant_loss(
    params: Params, cfg: ModelConfig, h: jax.Array, labels: jax.Array, n_tenants: int
) -> jax.Array:
    """(N,) masked-mean CE per tenant over a tenant-contiguous batch —
    tenant t's entry equals ``lm_loss`` on t's rows alone (the decoupling
    that makes fleet == per-tenant training)."""
    ll, cnt = lm_loss_rows(params, cfg, h, labels)
    ll = jnp.sum(ll.reshape(n_tenants, -1), axis=1)
    cnt = jnp.sum(cnt.reshape(n_tenants, -1), axis=1)
    return -ll / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Losses and steps
# ---------------------------------------------------------------------------


def blocked_skip_sum(
    acts: jax.Array, a_pool: jax.Array, b_pool: jax.Array, n_tenants: int
) -> jax.Array:
    """Grouped skip-sum specialised to the fleet's batch structure: rows are
    tenant-contiguous with a uniform per-tenant count, so the per-row pool
    gather of the general oracle collapses into a *batched einsum* over the
    tenant axis — the efficient dense (XLA) lowering on CPU/GPU, while the
    grouped Pallas kernel is the TPU one. Differentiable in the pools;
    activations are data.

    acts: (L, B, S, D) with B = n_tenants * bpt, tenant-major;
    a_pool: (N, L, D, R); b_pool: (N, L, R, D) -> (B, S, D).
    """
    acts = jax.lax.stop_gradient(acts)
    # Model-axis sessions keep the cached activations partitioned over L so
    # each shard sums its resident blocks' terms; the tenant-major einsum
    # below then needs exactly one cross-shard reduce for the (tmd) output.
    acts = constrain(acts, "layers", None, None, None)
    l, b, s, d = acts.shape
    at = acts.reshape(l, n_tenants, (b // n_tenants) * s, d)
    z = jnp.einsum("ltmd,tldr->tlmr", at, a_pool.astype(acts.dtype))
    out = jnp.einsum("tlmr,tlrd->tmd", z, b_pool.astype(acts.dtype))
    return out.astype(acts.dtype).reshape(b, s, d)


def _check_fleet_mode(sl: SL.SkipLoRAConfig) -> None:
    if sl.mode not in ("full", "int8"):
        raise ValueError(
            f"fleet training supports modes 'full' and 'int8', not {sl.mode!r}"
        )


def _fleet_skip_sum(
    stacked: Params,
    row_tenant: jax.Array,
    n_tenants: int,
    dtype,
    *,
    acts: Optional[jax.Array] = None,          # (L, B, S, D) float
    acts_q: Optional[jax.Array] = None,        # (L, B, S, D) int8
    acts_scale: Optional[jax.Array] = None,    # (L, B, S) fp32
    use_kernel: bool = True,
    freeze_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """One grouped skip-sum for a fleet batch, kernel or dense path.

    ``use_kernel=True`` routes to the grouped custom-VJP kernels (raw int8
    payload stays raw — dequant fused); ``use_kernel=False`` takes the
    ``blocked_skip_sum`` batched einsum the fleet's uniform tenant-
    contiguous batches allow (int8 payloads dequantise first)."""
    if use_kernel:
        if acts_q is not None:
            return skip_lora_grouped_train_int8(
                acts_q, acts_scale, stacked["A"], stacked["B"], row_tenant,
                freeze_mask=freeze_mask,
            )
        return skip_lora_grouped_train(
            acts, stacked["A"], stacked["B"], row_tenant, freeze_mask=freeze_mask
        )
    a_pool, b_pool = stacked["A"], stacked["B"]
    if freeze_mask is not None:
        from repro.kernels.skip_lora.ops import freeze_pool_slots

        a_pool = freeze_pool_slots(a_pool, freeze_mask)
        b_pool = freeze_pool_slots(b_pool, freeze_mask)
    if acts_q is not None:
        acts = (acts_q.astype(jnp.float32) * acts_scale[..., None]).astype(dtype)
    return blocked_skip_sum(acts, a_pool, b_pool, n_tenants)


def fleet_cached_loss(
    params: Params,
    cfg: ModelConfig,
    sl: SL.SkipLoRAConfig,
    stacked: Params,
    vals: dict[str, jax.Array],
    row_tenant: jax.Array,
    n_tenants: int,
    dtype,
    *,
    use_kernel: bool = True,
    freeze_mask: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Fleet loss from cached values: one grouped skip-sum for the whole
    batch, per-tenant reduction. Returns (sum of per-tenant losses,
    (N,) per-tenant losses)."""
    _check_fleet_mode(sl)
    if sl.mode == "int8":
        skip = _fleet_skip_sum(
            stacked, row_tenant, n_tenants, dtype,
            acts_q=jnp.swapaxes(vals["acts_q"], 0, 1),
            acts_scale=jnp.swapaxes(vals["acts_scale"], 0, 1),
            use_kernel=use_kernel, freeze_mask=freeze_mask,
        )
    else:
        skip = _fleet_skip_sum(
            stacked, row_tenant, n_tenants, dtype,
            acts=jnp.swapaxes(vals["acts"], 0, 1).astype(dtype),
            use_kernel=use_kernel, freeze_mask=freeze_mask,
        )
    h = vals["y_base"].astype(dtype) + skip.astype(dtype)
    per = per_tenant_loss(params, cfg, h, vals["labels"], n_tenants)
    return jnp.sum(per), per


def make_fleet_cached_step_from_vals(
    cfg: ModelConfig,
    sl: SL.SkipLoRAConfig,
    optimizer,
    n_tenants: int,
    *,
    use_kernel: bool = True,
    freeze_mask: Optional[jax.Array] = None,
):
    """One fleet adapter step from already-gathered cache values (the
    granularity the tiered engine's streaming read path feeds)."""
    dtype = model_dtype(cfg)

    def step(params, stacked, opt_state, vals, row_tenant):
        def loss_fn(t):
            return fleet_cached_loss(
                params, cfg, sl, t, vals, row_tenant, n_tenants, dtype,
                use_kernel=use_kernel, freeze_mask=freeze_mask,
            )

        (_, per), grads = jax.value_and_grad(loss_fn, has_aux=True)(stacked)
        updates, opt_state = optimizer.update(grads, opt_state, stacked)
        return apply_updates(stacked, updates), opt_state, per

    return step


def make_fleet_cached_epoch(
    cfg: ModelConfig,
    sl: SL.SkipLoRAConfig,
    optimizer,
    n_tenants: int,
    *,
    use_kernel: bool = True,
    freeze_mask: Optional[jax.Array] = None,
    donate: bool = True,
    jit: bool = True,
):
    """Whole fleet cached epoch as one ``lax.scan`` dispatch: cache gathers
    + grouped adapter steps, zero backbone compute, every tenant advanced
    per step. ``jit=False`` returns the raw function for callers that wrap
    the epoch themselves (e.g. a ``shard_map`` body).

    epoch: (params, stacked, opt_state, cache, idx_mat, row_tenant)
        -> (stacked, opt_state, losses (steps, N))
    """
    step = make_fleet_cached_step_from_vals(
        cfg, sl, optimizer, n_tenants,
        use_kernel=use_kernel, freeze_mask=freeze_mask,
    )

    def epoch(params, stacked, opt_state, cache, idx_mat, row_tenant):
        def body(carry, idx):
            t, o = carry
            t, o, per = step(params, t, o, cache_read(cache, idx), row_tenant)
            return (t, o), per

        (stacked, opt_state), losses = jax.lax.scan(
            body, (stacked, opt_state), idx_mat
        )
        return stacked, opt_state, losses

    if not jit:
        return epoch
    return jax.jit(epoch, donate_argnums=(1, 2) if donate else ())


def make_fleet_eval_loss(
    cfg: ModelConfig,
    sl: SL.SkipLoRAConfig,
    n_tenants: int,
    *,
    use_kernel: bool = True,
    jit: bool = True,
):
    """Per-tenant held-out loss from cached values — the shadow-eval body
    (DESIGN.md §13). The backbone term (``y_base``) is already in the cache
    from the populate forward, so eval is the same backbone-free grouped
    skip-sum + CE a cached training step runs, minus the gradient: zero
    extra forwards over the backbone, ever.

    eval_loss: (params, stacked, vals, row_tenant) -> (N,) per-tenant loss.
    """
    dtype = model_dtype(cfg)

    def eval_loss(params, stacked, vals, row_tenant):
        _, per = fleet_cached_loss(
            params, cfg, sl, stacked, vals, row_tenant, n_tenants, dtype,
            use_kernel=use_kernel,
        )
        return per

    return jax.jit(eval_loss) if jit else eval_loss


def make_fleet_cached_epoch_eval(
    cfg: ModelConfig,
    sl: SL.SkipLoRAConfig,
    optimizer,
    n_tenants: int,
    *,
    use_kernel: bool = True,
    eval_pre: bool = True,
    eval_post: bool = True,
    donate: bool = False,
):
    """``make_fleet_cached_epoch`` with shadow eval folded into the SAME
    fused dispatch: the held-out per-tenant loss is computed from the
    cached rows immediately before the epoch's scan (``eval_pre``) and/or
    immediately after it (``eval_post``) — one compiled program, so eval
    adds two cache gathers and two grouped skip-sums to an epoch of
    training steps, not an extra dispatch (and never a backbone forward).

    epoch: (params, stacked, opt_state, cache, idx_mat, row_tenant,
            eval_idx, eval_row_tenant)
        -> (stacked, opt_state, losses (steps, N), pre (N,)|None, post (N,)|None)
    """
    step = make_fleet_cached_step_from_vals(
        cfg, sl, optimizer, n_tenants, use_kernel=use_kernel
    )
    ev = make_fleet_eval_loss(
        cfg, sl, n_tenants, use_kernel=use_kernel, jit=False
    )

    def epoch(params, stacked, opt_state, cache, idx_mat, row_tenant,
              eval_idx, eval_row_tenant):
        def held_out(t):
            return ev(params, t, cache_read(cache, eval_idx), eval_row_tenant)

        pre = held_out(stacked) if eval_pre else None

        def body(carry, idx):
            t, o = carry
            t, o, per = step(params, t, o, cache_read(cache, idx), row_tenant)
            return (t, o), per

        (stacked, opt_state), losses = jax.lax.scan(
            body, (stacked, opt_state), idx_mat
        )
        post = held_out(stacked) if eval_post else None
        return stacked, opt_state, losses, pre, post

    return jax.jit(epoch, donate_argnums=(1, 2) if donate else ())


def make_fleet_populate_epoch(
    cfg: ModelConfig,
    sl: SL.SkipLoRAConfig,
    optimizer,
    n_tenants: int,
    *,
    use_kernel: bool = True,
    freeze_mask: Optional[jax.Array] = None,
    donate: bool = True,
    jit: bool = True,
):
    """Fleet populate epoch: ONE adapter-free backbone forward per fleet
    batch serves every tenant's rows (the backbone is tenant-independent —
    DESIGN.md §7), activations scatter into each tenant's cache partition,
    and the adapter step runs on the just-collected full-precision
    activations via the grouped VJP (``int8`` mode quantises only the cache
    write, like the single-tenant populate step).

    epoch: (params, stacked, opt_state, cache, tokens, labels, idx_mat,
            row_tenant) -> (stacked, opt_state, cache, losses (steps, N))
    """
    dtype = model_dtype(cfg)
    _check_fleet_mode(sl)

    def epoch(params, stacked, opt_state, cache, tokens, labels, idx_mat, row_tenant):
        def body(carry, idx):
            t, o, c = carry
            out = lm_forward(params, cfg, tokens[idx], mode="train", collect_acts=True)
            acts = jax.lax.stop_gradient(out["acts"])       # (L, B, S, D)
            y_base = jax.lax.stop_gradient(out["y_base"])   # (B, S, D)
            lab = labels[idx]
            values = SL._encode_acts(acts, None, sl)
            values["y_base"] = y_base
            values["labels"] = lab
            c = cache_write(c, idx, values)

            def loss_fn(tt):
                skip = _fleet_skip_sum(
                    tt, row_tenant, n_tenants, dtype, acts=acts.astype(dtype),
                    use_kernel=use_kernel, freeze_mask=freeze_mask,
                )
                h = y_base.astype(dtype) + skip.astype(dtype)
                per = per_tenant_loss(params, cfg, h, lab, n_tenants)
                return jnp.sum(per), per

            (_, per), grads = jax.value_and_grad(loss_fn, has_aux=True)(t)
            updates, o = optimizer.update(grads, o, t)
            return (apply_updates(t, updates), o, c), per

        (stacked, opt_state, cache), losses = jax.lax.scan(
            body, (stacked, opt_state, cache), idx_mat
        )
        return stacked, opt_state, cache, losses

    if not jit:
        return epoch
    return jax.jit(epoch, donate_argnums=(1, 2, 3) if donate else ())


def fleet_cached_epoch_via_engine(
    step,
    params: Params,
    stacked: Params,
    opt_state,
    engine,
    idx_mat,
    row_tenant: jax.Array,
) -> tuple[Params, Any, jax.Array]:
    """Streaming fleet cached epoch through a ``TieredCacheEngine`` — the
    path when the fleet's pooled activation cache exceeds the HBM budget.
    Per-batch engine reads with the *next* fleet batch prefetched on the
    background thread while the in-flight grouped step runs. ``step`` is a
    (jitted) ``make_fleet_cached_step_from_vals`` product."""
    pers = []
    for _, vals in engine.stream_batches(idx_mat):
        stacked, opt_state, per = step(params, stacked, opt_state, vals, row_tenant)
        pers.append(per)
    return stacked, opt_state, jnp.stack(pers)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetResult:
    adapters: Params                  # stacked {"A": (N, L, D, R), "B": ...}
    opt_state: Any
    losses: np.ndarray                # (epochs, steps, n_tenants)
    epoch_times_s: list[float]
    cache: SkipCache | None = None
    engine: Any = None


def fleet_finetune(
    key: jax.Array,
    cfg: ModelConfig,
    sl: SL.SkipLoRAConfig,
    params: Params,
    tokens: jax.Array,                # (n_tenants, n_per, seq) int32
    labels: jax.Array,                # (n_tenants, n_per, seq) int32
    *,
    epochs: int,
    batch_per_tenant: int,
    lr: float = 1e-3,
    optimizer=None,
    use_kernel: bool = True,
    freeze_mask: Optional[jax.Array] = None,
    engine=None,
    seed: int = 0,
) -> FleetResult:
    """Algorithm 1 for a whole fleet: epoch 0 populates every tenant's
    cache partition (one shared backbone dispatch per fleet batch); epochs
    >= 1 run cached grouped steps with zero backbone compute. Every epoch
    phase is one compiled dispatch. With ``engine`` (a ``TieredCacheEngine``
    laid out for ``n_tenants * n_per`` samples), populated rows are handed
    to the engine after epoch 0 and cached epochs run the streaming
    prefetch path instead of the fused scan.

    Like ``launch/finetune.py --hbm-mb``, the populate epoch itself still
    materialises the full fleet cache once (the fused populate scan carries
    it); the engine's budget governs the *steady state* — cached epochs.
    Fleets whose single populate epoch already exceeds device memory need
    a streaming populate (per-batch ``engine.write``), which trades the
    one-dispatch epoch for per-batch Python — not implemented here."""
    n_tenants, n_per, seq = tokens.shape
    batch_per_tenant = min(batch_per_tenant, n_per)  # fleet_index_matrix clamp
    stacked = init_fleet_adapters(key, cfg, sl, n_tenants)
    opt = optimizer if optimizer is not None else adamw(lr)
    opt_state = opt.init(stacked)
    row_tenant = fleet_row_tenant(n_tenants, batch_per_tenant)

    tokens_flat = tokens.reshape(n_tenants * n_per, seq)
    labels_flat = labels.reshape(n_tenants * n_per, seq)
    cache = SL.init_lm_cache(n_tenants * n_per, cfg, sl, seq)

    populate_epoch = make_fleet_populate_epoch(
        cfg, sl, opt, n_tenants, use_kernel=use_kernel, freeze_mask=freeze_mask
    )
    cached_epoch = make_fleet_cached_epoch(
        cfg, sl, opt, n_tenants, use_kernel=use_kernel, freeze_mask=freeze_mask
    )
    engine_step = None
    if engine is not None:
        engine_step = jax.jit(
            make_fleet_cached_step_from_vals(
                cfg, sl, opt, n_tenants,
                use_kernel=use_kernel, freeze_mask=freeze_mask,
            )
        )

    losses, times = [], []
    for e in range(epochs):
        idx_mat = fleet_index_matrix(
            e, n_tenants, n_per, batch_per_tenant, seed=seed
        )
        t0 = time.perf_counter()
        if e == 0:
            stacked, opt_state, cache, ls = populate_epoch(
                params, stacked, opt_state, cache,
                tokens_flat, labels_flat, jnp.asarray(idx_mat), row_tenant,
            )
        elif engine is None:
            stacked, opt_state, ls = cached_epoch(
                params, stacked, opt_state, cache, jnp.asarray(idx_mat), row_tenant
            )
        else:
            stacked, opt_state, ls = fleet_cached_epoch_via_engine(
                engine_step, params, stacked, opt_state, engine, idx_mat, row_tenant
            )
        jax.block_until_ready(ls)
        times.append(time.perf_counter() - t0)
        losses.append(np.asarray(ls))
        if e == 0 and engine is not None:
            # Hand the populated partitions to the placement engine (a
            # one-off staging cost, outside the epoch loop's steady state);
            # rows past the HBM budget spill to the host tier.
            for row in idx_mat:
                idx = jnp.asarray(row)
                engine.write(idx, cache_read(cache, idx))
            cache = None  # engine owns placement now

    return FleetResult(
        adapters=stacked,
        opt_state=opt_state,
        losses=np.stack(losses),
        epoch_times_s=times,
        cache=cache,
        engine=engine,
    )


def write_back_to_pool(pool, tenants, stacked: Params) -> list[int]:
    """Install a fleet's trained slots into a serving ``AdapterPool`` as one
    batched in-place (donated) write; tenant ``tenants[i]`` gets stack row
    i. Returns the assigned slot indices."""
    return pool.register_many(tenants, stacked)
