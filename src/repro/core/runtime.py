"""Unified continual-learning runtime: serve + fleet fine-tune, one engine.

The paper's deployment story is continual (DESIGN.md §9): a device serves
with its adapter, accumulates new samples into the skip-cache, and
periodically fine-tunes. After PR 2/3 the repo had three disjoint entry
points (``launch/serve.py``, ``launch/finetune.py``, ``launch/fleet.py``)
that each rebuilt their own compiled functions, cache views, and pool
bookkeeping — serve and train could not interleave over one adapter pool.

``SessionRuntime`` is the single engine behind all three launchers. It owns

  - ONE ``AdapterPool`` (slot-based serving registry, now with session
    pinning so LRU eviction can never drop in-flight training state),
  - ONE ``TieredCacheEngine`` (every tenant's skip-cache partition), and
  - ONE compiled-function cache (module-level ``compiled``; the serve
    prefill/decode jits previously private to ``launch/serve.py`` live
    here, alongside the fleet epoch/step jits),

and processes an interleaved event stream:

  - ``serve(tenants, prompts)``: scan-fused generation, routed per batch —
    single-stack when every row is the base model, grouped (float or raw
    int8 pool layout) otherwise. Same compiled entries as PR 2's
    ``decode_scan`` benchmarks, so routing adds only a pool lookup.
  - ``ingest(tenant, tokens, labels)``: populate-phase forward that writes
    the tenant's skip-cache partition *and* returns last-position adapted
    logits — ingestion doubles as serving (``models.lm.ingest_prefill``).
  - ``adapt(tenants, epochs)``: cached-phase fleet epochs over the grouped
    custom-VJP kernels, write-back through ``AdapterPool.register_many``.
    Because the backbone is frozen, cached values equal the populate
    epoch's in-flight activations bitwise (full mode, matching cache
    dtype), so an interleaved serve -> ingest -> adapt session reproduces
    the offline ``fleet_finetune`` adapters *bitwise* on the kernel path —
    the §9 parity bar, enforced by ``tests/test_runtime.py``.

Batch planning goes through ``core.batch_plan`` with explicit tenant
partitions, so an ``adapt`` group that is a subset or reordering of the
ingested tenants still replays each tenant's own RNG stream.

Since the mesh-native refactor (DESIGN.md §10) every session is
constructed over an explicit device ``Mesh``:

  - a 1-device mesh (the default) reproduces the single-device session
    *bitwise* — the sharded paths collapse to the PR 4 code path;
  - on an N-way ``data`` axis the stacked adapter pool, optimizer moments,
    and skip-cache partitions shard **by tenant**: ``ShardedAdapterPool``
    owns the slot->shard placement, each logical shard's pool + cache
    engine + backbone replica is committed to its physical device, and
    serve/adapt batches route rows to the shard holding their slot;
  - ``adapt`` groups tenants by (trajectory, shard) and dispatches each
    group's fused epochs entirely on its shard — the same compiled entries
    as the 1-device path, with committed inputs, so there is never a
    cross-device gather of cache rows or adapter grads, and moving a group
    between devices is *bitwise free* (measured; this is why the sharded
    session hand-rolls its SPMD instead of using ``shard_map``, whose
    repartitioned programs drift at ~1e-6 — see §10);
  - the logical shard count (``placement_shards``) is a session-*layout*
    property carried through checkpoints: an elastic restart restores onto
    however many devices survive (shard ``s`` -> ``devices[s % n]``) and
    continues bitwise.
"""

from __future__ import annotations

import dataclasses
from collections import Counter, OrderedDict
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batch_plan
from repro.core import fleet_finetune as FF
from repro.core import lm_skiplora as SL
from repro.core.adapter_pool import ShardedAdapterPool
from repro.core.cache_engine import CacheStats, TieredCacheEngine
from repro.core.control_plane import ControlConfig, ControlPlane
from repro.models.config import ModelConfig
from repro.runtime.sharding import (
    ShardScope,
    make_mesh,
    replicate_backbone,
    scope_ctx,
    session_devices,
    session_mesh_layout,
    session_param_specs,
    shard_backbone,
    shard_submesh,
    specs_all_replicated,
)
from repro.models.lm import (
    decode_scan,
    ingest_prefill,
    init_serve_caches,
    pipeline_stage_params,
    sample_token,
    serve_decode,
    serve_prefill,
    serve_prefill_grouped,
)
from repro.optim.optimizers import OptState, adamw

Params = Any

# ---------------------------------------------------------------------------
# Shared compiled-function cache (one per process, every engine routes here)
# ---------------------------------------------------------------------------

#: (name, cfg, extras) -> jitted callable. cfg is a frozen dataclass and
#: hashes by value; jax.jit then keys compiled traces by argument shape
#: below this cache, so repeated calls at a new (batch, seq) retrace but
#: never rebuild the jit wrapper itself.
_FN_CACHE: dict[tuple, Any] = {}

#: Trace-time retrace counter: ``_mark_trace(name)`` runs as a Python side
#: effect INSIDE a jitted function body, so it fires exactly once per trace
#: (first call and every shape/static-arg retrace) and never on cache hits.
#: Tests assert e.g. that serving three distinct temperatures leaves
#: ``TRACE_COUNTS["decode_scan"]`` unchanged after warmup — the
#: recompile-per-temperature bug regression bar — without reaching into
#: jit's private ``_cache_size``.
TRACE_COUNTS: Counter = Counter()


def _mark_trace(name: str) -> None:
    TRACE_COUNTS[name] += 1


def compiled(key: tuple, make: Callable[[], Any]):
    """Fetch-or-build a jitted callable under a hashable key. The single
    compiled-fn cache behind serve, ingest, and adapt — the per-launcher
    caches of PR 2/3 collapsed here."""
    fn = _FN_CACHE.get(key)
    if fn is None:
        fn = _FN_CACHE[key] = make()
    return fn


def _cached_fn(name: str, cfg, make, extras: tuple = ()):
    return compiled((name, cfg, *extras), make)


# Every compiled-fn factory takes an optional ``scope`` (a hashable
# ``ShardScope`` or None): the fn body runs under ``scope_ctx(scope)`` so the
# model's ``constrain`` calls see the scope AT TRACE TIME — whenever jit
# retraces (new shapes, new statics), not just on the first call — and the
# scope rides the cache key so a 2-D session and a 1-device session never
# share a trace. ``scope=None`` traces with no constraints: bitwise the
# historical single/data-axis programs.


def _prefill_fn(cfg, scope=None):
    def make():
        def f(params, tokens, caches, adapters):
            with scope_ctx(scope):
                return serve_prefill(
                    params, cfg, tokens, caches, adapters=adapters
                )

        return jax.jit(f)

    return _cached_fn("prefill", cfg, make, (scope,))


def _prefill_grouped_fn(cfg, use_kernel: bool, scope=None):
    def make():
        def f(params, tokens, caches, pools, idx):
            with scope_ctx(scope):
                return serve_prefill_grouped(
                    params, cfg, tokens, caches, pools, idx,
                    use_kernel=use_kernel,
                )

        return jax.jit(f)

    return _cached_fn("prefill_grouped", cfg, make, (use_kernel, scope))


def _decode_scan_fn(cfg, use_kernel: bool = True, fuse_skip: bool = False,
                    scope=None):
    def make():
        def f(params, tok0, pos0, caches, key, adapters, pools, idx,
              max_new, temperature, unroll):
            _mark_trace("decode_scan")
            with scope_ctx(scope):
                return decode_scan(
                    params, cfg, tok0, pos0, caches, key,
                    max_new=max_new, temperature=temperature,
                    adapters=adapters, pools=pools, idx=idx,
                    use_kernel=use_kernel, fuse_skip=fuse_skip, unroll=unroll,
                )

        # Donate the KV caches: the scan's carry updates them in place.
        # ``temperature`` (arg 9) is deliberately NOT static: baking it into
        # the trace cache meant one full decode recompile per distinct
        # sampling temperature under live traffic. It is traced now (the
        # greedy/temperature select runs inside ``sample_token``), so every
        # temperature shares one compiled decode.
        return jax.jit(
            f,
            static_argnums=(8, 10),
            donate_argnums=(3,),
        )

    return _cached_fn("decode_scan", cfg, make, (use_kernel, fuse_skip, scope))


def _decode_step_fn(cfg, scope=None):
    def make():
        def f(params, tok, pos, caches, adapters):
            with scope_ctx(scope):
                return serve_decode(
                    params, cfg, tok, pos, caches, adapters=adapters
                )

        return jax.jit(f)

    return _cached_fn("decode_step", cfg, make, (scope,))


def _ingest_fn(cfg, use_kernel: bool, scope=None):
    def make():
        def f(params, tokens, pools, idx):
            with scope_ctx(scope):
                return ingest_prefill(
                    params, cfg, tokens, pools, idx, use_kernel=use_kernel
                )

        return jax.jit(f)

    return _cached_fn("ingest", cfg, make, (use_kernel, scope))


# ---------------------------------------------------------------------------
# Generation entry points (moved from launch/serve.py; the CLI re-exports)
# ---------------------------------------------------------------------------

#: Monotone counter behind ``_default_rng``: calls that omit ``rng`` used to
#: all fall back to ``jax.random.key(0)``, so every temperature>0 serve
#: without an explicit key replayed the SAME sample stream. Each omission now
#: folds a fresh counter value into the base key — still deterministic for a
#: fresh process (call N always sees fold_in(key(0), N)), never shared
#: between calls.
_DEFAULT_RNG_CALLS = 0


def _default_rng() -> jax.Array:
    global _DEFAULT_RNG_CALLS
    key = jax.random.fold_in(jax.random.key(0), _DEFAULT_RNG_CALLS)
    _DEFAULT_RNG_CALLS += 1
    return key


def generate(
    params,
    cfg,
    tokens,
    *,
    max_new: int,
    adapters_stack=None,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    unroll: int = 1,
    scope=None,
):
    """Batched generation, scan-fused: 1 prefill dispatch + 1 decode-scan
    dispatch for all ``max_new`` tokens. Returns (B, max_new) int32.
    ``scope`` (a ``ShardScope``) traces the dispatches with that mesh's
    activation constraints — required when ``params`` is model-axis
    sharded."""
    b, s = tokens.shape
    caches = init_serve_caches(cfg, b, s + max_new)
    logits, caches = _prefill_fn(cfg, scope)(
        params, tokens, caches, adapters_stack
    )
    tok0, key = sample_token(
        logits, rng if rng is not None else _default_rng(), temperature
    )
    toks, _ = _decode_scan_fn(cfg, scope=scope)(
        params, tok0, jnp.asarray(s, jnp.int32), caches, key,
        adapters_stack, None, None, max_new,
        jnp.asarray(temperature, jnp.float32), unroll,
    )
    return toks


def generate_grouped(
    params,
    cfg,
    tokens,
    pools: dict[str, jax.Array],
    idx: jax.Array,
    *,
    max_new: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    use_kernel: bool = True,
    fuse_skip: bool = False,
    unroll: int = 1,
    scope=None,
):
    """Multi-tenant generation: batch row b decodes under adapter slot
    idx[b] gathered from the stacked pool (float, raw-int8, or packed-4-bit
    layout, see ``AdapterPool.pools()``). Same two-dispatch structure as
    ``generate``. ``fuse_skip`` inlines the decode skip term as dense math
    (one fused XLA step program instead of backbone + grouped kernel);
    prefill keeps the grouped kernel either way. ``scope`` traces with a
    model-axis mesh's activation constraints (sharded-backbone serving)."""
    b, s = tokens.shape
    caches = init_serve_caches(cfg, b, s + max_new)
    logits, caches = _prefill_grouped_fn(cfg, use_kernel, scope)(
        params, tokens, caches, pools, idx
    )
    tok0, key = sample_token(
        logits, rng if rng is not None else _default_rng(), temperature
    )
    toks, _ = _decode_scan_fn(cfg, use_kernel, fuse_skip, scope)(
        params, tok0, jnp.asarray(s, jnp.int32), caches, key,
        None, pools, idx, max_new,
        jnp.asarray(temperature, jnp.float32), unroll,
    )
    return toks


def generate_loop(
    params,
    cfg,
    tokens,
    *,
    max_new: int,
    adapters_stack=None,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
):
    """Per-token Python decode loop (the pre-scan path, kept for the
    loop-vs-scan benchmark): ``max_new`` dispatches, cached step jits."""
    b, s = tokens.shape
    caches = init_serve_caches(cfg, b, s + max_new)
    prefill = _prefill_fn(cfg)
    decode = _decode_step_fn(cfg)
    logits, caches = prefill(params, tokens, caches, adapters_stack)
    key = rng if rng is not None else _default_rng()
    tok, key = sample_token(logits, key, temperature)
    out = []
    for i in range(max_new):
        out.append(tok)
        logits, caches = decode(
            params, tok, jnp.asarray(s + i, jnp.int32), caches, adapters_stack
        )
        tok, key = sample_token(logits, key, temperature)
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# Session runtime
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TenantState:
    """Per-tenant continual-learning state the runtime tracks between
    events. ``adapters``/``opt_*`` are per-tenant slices of the stacked
    fleet trees (flat {"A": (L,D,R), "B": (L,R,D)} layout)."""

    partition: int                      # cache partition index
    n_ingested: int = 0                 # rows written into the partition
    epochs_done: int = 0                # planner epoch stream position
    step: int = 0                       # optimizer step count
    adapters: Optional[Params] = None
    opt_mu: Optional[Params] = None
    opt_nu: Optional[Params] = None

    @property
    def trained(self) -> bool:
        return self.adapters is not None


class SessionRuntime:
    """One session engine for serve + ingest + adapt over a shared pool,
    constructed over an explicit device mesh.

    ``max_tenants`` bounds the cache partitions (``samples_per_tenant``
    rows each, global id = partition * samples_per_tenant + local id — the
    PR 3 fleet convention, so offline and interleaved training address
    identical cache rows). The pool defaults to ``max_tenants/shards + 1``
    slots per shard (slot 0 pinned zero, ``pool_slots`` overrides the
    per-shard count); the engine to fully HBM-resident — pass
    ``cache_capacity`` / ``hbm_budget_bytes`` to force tiered placement,
    which flips ``adapt`` from the fused-scan epoch to the streaming
    prefetch path (DESIGN.md §9 path table).

    ``mesh`` (default: a 1-device ``("data",)`` mesh — today's behaviour,
    bitwise) supplies the physical devices; ``placement_shards`` fixes the
    *logical* shard count (default: the mesh's device count). Partition
    ``p`` belongs to logical shard ``p % placement_shards``, logical shard
    ``s`` lives on ``devices[s % n_devices]`` — so a checkpoint restored
    onto a different device count keeps its layout, its group traces, and
    therefore its trajectory, bitwise (DESIGN.md §10). Backbone placement
    is derived from the ``runtime.sharding`` rule table
    (``session_param_specs``): all-replicated on a data-only mesh, realised
    as per-shard committed replicas.

    On a 2-D ``(data, model)`` mesh each logical shard instead owns a
    model-axis device *group* holding ONE Megatron-sharded backbone replica
    (``shard_backbone`` over the shard's submesh): per-device backbone
    bytes drop ~Mx and every serve/ingest/adapt dispatch traces under the
    shard's ``ShardScope`` so activations carry the matching constraints.
    ``pipeline_stages=N`` (N == model-axis size) additionally precomputes a
    GPipe stage split of the backbone for the scheduler's pipelined
    admission prefill (``models.lm.pipeline_sched_prefill``).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        sl: SL.SkipLoRAConfig,
        params: Params,
        *,
        max_tenants: int,
        samples_per_tenant: int,
        seq: int,
        lr: float = 1e-3,
        optimizer=None,
        pool_slots: Optional[int] = None,
        pool_compress: Optional[str] = None,
        cache_capacity: Optional[int] = None,
        hbm_budget_bytes: Optional[int] = None,
        cache_dir: Optional[str] = None,
        use_kernel: bool = True,
        decode_fuse: bool = False,
        seed: int = 0,
        mesh=None,
        placement_shards: Optional[int] = None,
        pipeline_stages: int = 0,
        idx_memo_slots: int = 256,
        control: Optional[ControlConfig] = None,
    ):
        if sl.mode not in ("full", "int8"):
            raise ValueError(
                f"the session runtime trains fleet modes 'full'/'int8', "
                f"not {sl.mode!r}"
            )
        self.cfg, self.sl = cfg, sl
        self.max_tenants = max_tenants
        self.samples_per_tenant = samples_per_tenant
        self.seq = seq
        self.use_kernel = use_kernel
        # Inline the decode skip term as dense math (one fused step program)
        # instead of a grouped kernel dispatch — temp-0 tokens are identical
        # either way; see models.lm.decode_step.
        self.decode_fuse = decode_fuse
        self.seed = seed
        self.optimizer = optimizer if optimizer is not None else adamw(lr)
        self._opt_key = ("adamw", lr) if optimizer is None else ("custom", id(optimizer))
        #: Adapter control plane (DESIGN.md §13) — strictly opt-in: with
        #: ``control=None`` (the default) the session plans, trains, and
        #: writes back bitwise the historical trajectory. With a
        #: ``ControlConfig``: every tenant's epoch plan excludes its
        #: held-out rows, ``adapt`` computes pre/post shadow-eval loss in
        #: the same fused dispatch as training, and write-back is gated.
        self.control_cfg = control
        self.control = ControlPlane(control) if control is not None else None

        # -- mesh + logical shard layout ------------------------------------
        if mesh is None:
            mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
        self.mesh = mesh
        self.devices = session_devices(mesh)
        n_groups = len(self.devices)
        _, n_model, _ = session_mesh_layout(mesh)
        self.model_parallel = n_model
        self.pipeline_stages = int(pipeline_stages)
        self.n_shards = (
            int(placement_shards) if placement_shards is not None
            else n_groups
        )
        if self.n_shards < 1:
            raise ValueError(f"placement_shards {self.n_shards} < 1")
        if max_tenants % self.n_shards:
            raise ValueError(
                f"max_tenants {max_tenants} must divide over "
                f"{self.n_shards} shards"
            )
        if self.pipeline_stages:
            if self.pipeline_stages != n_model or n_model < 2:
                raise ValueError(
                    f"pipeline_stages={self.pipeline_stages} must equal the "
                    f"mesh's model-axis size ({n_model}, >= 2): the stages "
                    "repurpose each shard's tensor-parallel device group"
                )
            if pool_compress is not None:
                raise ValueError(
                    "pipeline serve reads the adapter pool per stage and "
                    "needs the float layout: pool_compress must be None"
                )
        if n_model > 1:
            # 2-D (data x model) mesh: each logical shard's backbone is ONE
            # Megatron-sharded replica over its model-axis device group (the
            # ``data`` axis still shards tenants exactly as PR 5). The
            # grouped Pallas kernels don't partition under GSPMD, so 2-D
            # sessions take the dense skip-sum paths.
            if use_kernel:
                raise ValueError(
                    "grouped Pallas kernels do not partition over a model "
                    "axis; build (data, model) sessions with use_kernel=False"
                )
            submeshes = [
                shard_submesh(mesh, s % n_groups) for s in range(self.n_shards)
            ]
            self._scope = [ShardScope(sm) for sm in submeshes]
            # Per-shard "device" becomes a replicated NamedSharding over the
            # shard's submesh: every existing device_put call site (pool,
            # cache engine, adapt state) then commits its arrays onto the
            # whole group, which is what lets them enter one jit alongside
            # the model-sharded backbone.
            self._shard_device = [
                jax.sharding.NamedSharding(sm, jax.sharding.PartitionSpec())
                for sm in submeshes
            ]
            self._shard_params = []
            for s in range(self.n_shards):
                self._shard_params.append(
                    shard_backbone(params, submeshes[s]) if s < n_groups
                    else self._shard_params[s % n_groups]
                )
        else:
            self._scope = [None] * self.n_shards
            self._shard_device = [
                self.devices[s % n_groups] for s in range(self.n_shards)
            ]
            # Backbone placement from the runtime.sharding rule table: on a
            # data-only session mesh every AxisRules-derived spec resolves to
            # replication, which replicate_backbone realises as one committed
            # replica per device.
            assert specs_all_replicated(session_param_specs(params, mesh))
            replicas = replicate_backbone(params, self.devices)
            self._shard_params = [
                replicas[s % n_groups] for s in range(self.n_shards)
            ]
        self.params = self._shard_params[0]
        # Pipeline partitioning of the same submesh devices: the backbone
        # re-stacked into n_stages contiguous layer blocks, leading axis
        # sharded over the (renamed-in-place) model axis so stage i's block
        # lives wholly on device i of each shard's group.
        self._stage_blocks: list = [None] * self.n_shards
        self._stage_valid: list = [None] * self.n_shards
        if self.pipeline_stages:
            blocks, valid = pipeline_stage_params(
                params, cfg, self.pipeline_stages
            )
            for s in range(self.n_shards):
                if s < n_groups:
                    stage_sh = jax.sharding.NamedSharding(
                        submeshes[s], jax.sharding.PartitionSpec("model")
                    )
                    self._stage_blocks[s] = jax.tree.map(
                        lambda x: jax.device_put(x, stage_sh), blocks
                    )
                    self._stage_valid[s] = jax.device_put(valid, stage_sh)
                else:
                    self._stage_blocks[s] = self._stage_blocks[s % n_groups]
                    self._stage_valid[s] = self._stage_valid[s % n_groups]

        # -- per-shard engines, pools, partitions ---------------------------
        tenants_per_shard = max_tenants // self.n_shards
        shard_samples = tenants_per_shard * samples_per_tenant
        if cache_capacity is None and hbm_budget_bytes is None:
            shard_capacity = shard_samples  # fully resident: fused-scan adapt
        elif cache_capacity is not None:
            shard_capacity = max(1, cache_capacity // self.n_shards)
        else:
            shard_capacity = None
        shard_budget = (
            None if hbm_budget_bytes is None
            else max(1, hbm_budget_bytes // self.n_shards)
        )
        layout = SL.lm_cache_layout(cfg, sl, seq)
        self.engines = [
            TieredCacheEngine(
                shard_samples,
                layout,
                capacity=shard_capacity,
                hbm_budget_bytes=shard_budget,
                directory=(
                    cache_dir if cache_dir is None or self.n_shards == 1
                    else f"{cache_dir}/shard_{s}"
                ),
                device=self._shard_device[s],
            )
            for s in range(self.n_shards)
        ]
        self.engine = self.engines[0]  # 1-shard alias (the PR 4 surface)
        self.pool = ShardedAdapterPool(
            pool_slots if pool_slots is not None else tenants_per_shard + 1,
            cfg, sl.rank, n_shards=self.n_shards,
            devices=self._shard_device, compress=pool_compress,
            history=control.history_depth if control is not None else 0,
        )
        self._tenants: dict[Any, TenantState] = {}
        #: Per-shard free cache partitions (global partition ids; partition
        #: p belongs to shard p % n_shards). Popped smallest-first, like the
        #: PR 4 single list.
        self._free_partitions = [
            [p for p in range(max_tenants - 1, -1, -1) if p % self.n_shards == s]
            for s in range(self.n_shards)
        ]
        #: Per-shard adapt scan-path cache views (export_skipcache memo).
        self._export: list[Optional[Any]] = [None] * self.n_shards
        #: (shard, tenant tuple, shard version) -> device idx array, LRU.
        #: Repeated serve batches skip the per-call host->device slot-index
        #: transfer; any slot-map change bumps the version and invalidates.
        #: Live traffic produces unboundedly many distinct tenant orderings
        #: (and version bumps strand old entries), so the memo is bounded at
        #: ``idx_memo_slots``: hits refresh recency, misses evict the
        #: least-recently-used entry once full. ``counters`` tracks
        #: ``idx_memo/{hits,misses,evictions}``.
        if idx_memo_slots < 1:
            raise ValueError(f"idx_memo_slots {idx_memo_slots} < 1")
        self._idx_cache: OrderedDict[tuple, jax.Array] = OrderedDict()
        self._idx_cache_cap = int(idx_memo_slots)
        #: Serve-call counter behind the per-session default rng: serve()
        #: with rng=None derives fold_in(key(seed), counter) — deterministic
        #: replay for an identically-seeded fresh session, never the same
        #: key twice within one session.
        self._serve_calls = 0
        self._scheduler = None
        #: Per-shard paged KV block pools + radix prefix indexes (the
        #: scheduler's prefix-reuse state; see ``core.kv_pool`` /
        #: ``core.prefix_index``). Lazily built by ``kv_pool()`` so
        #: reuse-off sessions pay nothing.
        self._kv_pools: dict[int, Any] = {}
        self._prefix_indexes: dict[int, Any] = {}
        self.counters = Counter()

    # -- shard arithmetic ----------------------------------------------------

    def _shard_of_partition(self, partition: int) -> int:
        return partition % self.n_shards

    def _local_ids(self, partition: int, rows) -> jax.Array:
        """Global partition + partition-local row ids -> shard-engine ids."""
        local_part = partition // self.n_shards
        return jnp.asarray(rows) + local_part * self.samples_per_tenant

    def _global_id(self, shard: int, local_id: int) -> int:
        part = (local_id // self.samples_per_tenant) * self.n_shards + shard
        return part * self.samples_per_tenant + local_id % self.samples_per_tenant

    # -- tenant bookkeeping --------------------------------------------------

    def tenant(self, tenant) -> TenantState:
        st = self._tenants.get(tenant)
        if st is None:
            raise KeyError(f"unknown tenant {tenant!r}")
        return st

    def _add_tenant(self, tenant) -> TenantState:
        shard = self.pool.place(tenant)
        if not self._free_partitions[shard]:
            raise RuntimeError(
                f"session full: all "
                f"{self.max_tenants // self.n_shards} cache partitions of "
                f"shard {shard} in use ({self.max_tenants} session-wide)"
            )
        st = TenantState(partition=self._free_partitions[shard].pop())
        self._tenants[tenant] = st
        return st

    def release(self, tenant) -> None:
        """Drop a tenant's training state and cache partition (its pool slot
        — if any — stays registered but is unpinned, so normal LRU applies
        again; a slot-less tenant loses its shard placement too)."""
        st = self._tenants.pop(tenant)
        self._free_partitions[self._shard_of_partition(st.partition)].append(
            st.partition
        )
        if self.pool.has(tenant):
            self.pool.unpin(tenant)
        else:
            self.pool.unplace(tenant)
        for idx in self._prefix_indexes.values():
            idx.drop_tenant(tenant)

    # -- paged KV prefix cache ----------------------------------------------

    def kv_pool(self, shard: int, *, block: Optional[int] = None,
                n_blocks: Optional[int] = None):
        """The shard's paged KV block pool, built on first call (on the
        shard's device). ``block`` is the pool's identity — a later caller
        asking for a different block size gets a loud error (tables and
        radix paths are block-granular); ``n_blocks`` is only a sizing
        hint for construction and is ignored once the pool exists."""
        from repro.core.kv_pool import KVBlockPool, get_default_block

        pool = self._kv_pools.get(shard)
        if pool is not None:
            if block is not None and int(block) != pool.block:
                raise ValueError(
                    f"kv pool shard {shard} already built with block="
                    f"{pool.block}; requested {block}"
                )
            return pool
        if n_blocks is None:
            raise ValueError(
                "first kv_pool() call for a shard must size it (n_blocks)"
            )
        pool = KVBlockPool(
            self.cfg, n_blocks=int(n_blocks),
            block=int(block) if block else get_default_block(),
            device=self._shard_device[shard],
        )
        self._kv_pools[shard] = pool
        return pool

    def prefix_index(self, shard: int):
        from repro.core.prefix_index import RadixPrefixIndex

        idx = self._prefix_indexes.get(shard)
        if idx is None:
            pool = self._kv_pools.get(shard)
            if pool is None:
                raise ValueError(
                    f"prefix_index({shard}) needs kv_pool({shard}, ...) "
                    "built first"
                )
            idx = self._prefix_indexes[shard] = RadixPrefixIndex(pool)
        return idx

    def reset_prefix_cache(self) -> None:
        """Forget every pooled prefix (all shards): radix trees cleared,
        pool refcounts zeroed, generations bumped so in-flight handles
        turn stale. The benchmark calls this between replays so each
        measurement starts cold."""
        for shard, pool in self._kv_pools.items():
            idx = self._prefix_indexes.get(shard)
            if idx is not None:
                idx.reset()
            else:
                pool.reset()

    def check_prefix_no_leaks(self) -> None:
        """Drained-state ref invariant, raised on violation: every held
        block is owned by exactly one radix node and nothing else (no
        in-flight refs survive a drain; free + held == n_blocks)."""
        for shard, pool in self._kv_pools.items():
            idx = self._prefix_indexes.get(shard)
            pool.check_no_leaks(idx.n_nodes() if idx is not None else 0)
            extra = int(pool.refs.sum()) - int((pool.refs > 0).sum())
            if extra:
                raise RuntimeError(
                    f"kv pool shard {shard}: {extra} in-flight ref(s) "
                    "outstanding after drain"
                )

    # -- events --------------------------------------------------------------

    def serve(
        self,
        tenants: Sequence,
        prompts: jax.Array,
        *,
        max_new: int,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        unroll: int = 1,
    ) -> jax.Array:
        """Scan-fused generation for a mixed-tenant batch. Row b decodes
        under ``tenants[b]``'s pool slot (``None`` -> base model). Routes
        the single-stack path when the whole batch is base traffic, the
        grouped (float/int8) path otherwise — always through the shared
        compiled-fn cache, so the runtime adds only a pool lookup over
        calling ``generate``/``generate_grouped`` directly. On a
        multi-shard session the batch additionally splits by slot shard:
        each shard decodes its own rows against its local pool segment on
        its own device (one async dispatch per shard, no cross-device
        adapter gather), and the rows stitch back in order."""
        if len(tenants) != prompts.shape[0]:
            raise ValueError(
                f"{len(tenants)} tenants for batch {prompts.shape[0]}"
            )
        if rng is None:
            # Counter-derived per-session key: repeated temperature>0 serves
            # without an explicit rng must not replay one sample stream, but
            # an identically-seeded fresh session must still reproduce this
            # one (the multi-shard fold_in(rng, s) split below then stays
            # consistent with the single-shard stream by construction).
            rng = jax.random.fold_in(
                jax.random.key(self.seed), self._serve_calls
            )
        self._serve_calls += 1
        if all(t is None for t in tenants):
            path = "serve/single/base"
            toks = generate(
                self.params, self.cfg, prompts, max_new=max_new,
                temperature=temperature, rng=rng, unroll=unroll,
                scope=self._scope[0],
            )
        else:
            variant = "int8" if self.pool.compress == "int8" else "float"
            path = f"serve/grouped/{variant}"
            if self.n_shards == 1:
                toks = self._serve_shard(
                    0, tenants, prompts, max_new=max_new,
                    temperature=temperature, rng=rng, unroll=unroll,
                )
            else:
                parts = []
                for s, (rows, subs) in enumerate(self.pool.route(tenants)):
                    if not rows:
                        continue
                    sub_rng = None if rng is None else jax.random.fold_in(rng, s)
                    parts.append((rows, self._serve_shard(
                        s, subs, prompts[np.asarray(rows)], max_new=max_new,
                        temperature=temperature, rng=sub_rng, unroll=unroll,
                    )))
                    self.counters["serve/shard_dispatches"] += 1
                out = np.zeros((len(tenants), max_new), np.int32)
                for rows, sub_toks in parts:  # dispatched above, sync here
                    out[np.asarray(rows)] = np.asarray(sub_toks)
                toks = jnp.asarray(out)
        self.counters[path] += 1
        self.counters["serve/tokens"] += int(toks.size)
        return toks

    def _serve_shard(
        self, s: int, tenants, prompts, *, max_new, temperature, rng, unroll
    ) -> jax.Array:
        """Grouped decode of one shard's rows against its pool segment (on
        a 1-shard session this IS the PR 4 grouped path, bitwise)."""
        key_ = (s, tuple(tenants), self.pool.shards[s].version)
        idx = self._idx_cache.get(key_)
        if idx is None:
            self.counters["idx_memo/misses"] += 1
            while len(self._idx_cache) >= self._idx_cache_cap:
                self._idx_cache.popitem(last=False)  # evict LRU, keep rest
                self.counters["idx_memo/evictions"] += 1
            idx = self._idx_cache[key_] = self.pool.lookup_local(s, tenants)
        else:
            self.counters["idx_memo/hits"] += 1
            self._idx_cache.move_to_end(key_)
            self.pool.touch(tenants)  # recency still tracks traffic
        return generate_grouped(
            self._shard_params[s], self.cfg, prompts,
            self.pool.shard_pools(s), idx,
            max_new=max_new, temperature=temperature, rng=rng,
            use_kernel=self.use_kernel, fuse_skip=self.decode_fuse,
            unroll=unroll, scope=self._scope[s],
        )

    # -- request-level surface (continuous batching; core.scheduler) ---------

    def attach_scheduler(self, **kw):
        """Construct the session's ``RequestScheduler`` with explicit
        limits (see ``core.scheduler.RequestScheduler``). The batch-level
        ``serve``/``ingest`` calls above stay available alongside it —
        the scheduler is a front door, not a replacement."""
        from repro.core.scheduler import RequestScheduler

        if self._scheduler is not None:
            raise RuntimeError("session already has a scheduler attached")
        self._scheduler = RequestScheduler(self, **kw)
        return self._scheduler

    @property
    def scheduler(self):
        """The attached scheduler (default limits if never configured)."""
        if self._scheduler is None:
            self.attach_scheduler()
        return self._scheduler

    def enqueue_serve(self, tenant, prompt, *, max_new: int,
                      temperature: float = 0.0):
        """Queue one generation request; returns its ``Request`` future.
        Admission (per-tenant fairness, shard routing, row recycling) is
        the scheduler's; pump with ``drain()`` or ``scheduler.step()``."""
        return self.scheduler.submit(
            tenant, prompt, max_new=max_new, temperature=temperature
        )

    def enqueue_ingest(self, tenant, tokens, labels):
        """Queue fine-tuning ingestion to run at a step boundary between
        decode dispatches; returns its ``IngestRequest``."""
        return self.scheduler.submit_ingest(tenant, tokens, labels)

    def drain(self):
        """Run the scheduler until every queued request completes."""
        return self.scheduler.drain()

    def ingest(self, tenant, tokens: jax.Array, labels: jax.Array) -> jax.Array:
        """Populate-phase forward for new on-device samples: writes the
        batch into the tenant's skip-cache partition AND returns the
        last-position logits under the tenant's current adapters (zero slot
        until the first ``adapt`` write-back) — ingestion doubles as
        serving. Returns (B, 1, V) logits."""
        # Validate BEFORE registering: a rejected batch must not leak a
        # cache partition or leave a zombie tenant that poisons adapt().
        st = self._tenants.get(tenant)
        b, s = tokens.shape
        if s != self.seq:
            raise ValueError(f"seq {s} != session cache layout seq {self.seq}")
        filled = st.n_ingested if st is not None else 0
        if filled + b > self.samples_per_tenant:
            raise ValueError(
                f"tenant {tenant!r} partition full: {filled}+{b} > "
                f"{self.samples_per_tenant}"
            )
        if st is None:
            st = self._add_tenant(tenant)
        s = self._shard_of_partition(st.partition)
        logits, acts, y_base = self._populate(s, tenant, tokens)
        values = SL._encode_acts(acts, None, self.sl)
        values["y_base"] = y_base
        values["labels"] = labels
        ids = self._local_ids(
            st.partition, np.arange(st.n_ingested, st.n_ingested + b)
        )
        self.engines[s].write(ids, values)
        self._export[s] = None  # new rows: invalidate adapt's exported view
        st.n_ingested += b
        self.counters["ingest/rows"] += b
        return logits

    def score(self, tenant, tokens: jax.Array) -> jax.Array:
        """Last-position logits (B, 1, V) of ``tokens`` under ``tenant``'s
        served adapters (``None`` or a tenant without a slot: the base
        model) — the populate forward ``ingest`` runs, with no cache write
        and no tenant registration."""
        s = self.pool.shard_of(tenant) if (
            tenant is not None and self.pool.has(tenant)
        ) else 0
        return self._populate(s, tenant, tokens)[0]

    def _populate(self, s: int, tenant, tokens: jax.Array):
        """(logits, acts, y_base) of the shard-``s`` populate forward, each
        row under ``tenant``'s pool slot (the zero slot when it has none)."""
        who = [tenant if self.pool.has(tenant) else None] * tokens.shape[0]
        idx = self.pool.lookup_local(s, who)
        return _ingest_fn(self.cfg, self.use_kernel, self._scope[s])(
            self._shard_params[s], tokens, self.pool.shard_pools(s), idx
        )

    def adapt(
        self,
        tenants: Optional[Sequence] = None,
        *,
        epochs: int = 1,
        batch_per_tenant: int = 4,
        key: Optional[jax.Array] = None,
    ) -> dict:
        """Cached-phase fleet fine-tune over the tenants' ingested
        partitions: every epoch is grouped custom-VJP adapter steps with
        ZERO backbone compute (the cache already holds what the populate
        forward saw), then one batched donated write-back into the serving
        pool (``register_many``) and a pin on every trained slot.

        Tenants new to training draw initial adapters from ``key`` exactly
        like ``fleet_finetune`` (``init_fleet_adapters`` row i -> i-th
        tenant), and the planner replays each tenant's own RNG stream, so a
        fresh session's first ``adapt`` reproduces the offline trainer
        bitwise on the kernel path. Tenants are grouped by (optimizer step,
        epoch position, partition fill, shard) — only same-trajectory
        tenants can share a stacked optimizer's scalar step counter, and
        only same-shard tenants share a device. Every group's fused epochs
        dispatch entirely on its shard's device (committed inputs, the same
        compiled entries on every shard); groups on different shards
        overlap through jax's async dispatch — losses are pulled to host
        only after every group has been issued.

        Returns {"losses": {tenant: (epochs, steps) np.ndarray}, "groups":
        [group tenant lists], "path": "scan" | "stream"}.
        """
        order = [t for t in self._tenants] if tenants is None else list(tenants)
        if not order:
            raise ValueError("no tenants to adapt")
        for t in order:
            if self.tenant(t).n_ingested == 0:
                raise ValueError(f"tenant {t!r} has no ingested samples")

        # Fresh tenants draw stacked inits from one key, in call order.
        fresh = [t for t in order if not self.tenant(t).trained]
        if fresh:
            stacked0 = FF.init_fleet_adapters(
                key if key is not None else jax.random.key(self.seed),
                self.cfg, self.sl, len(fresh),
            )
            opt0 = self.optimizer.init(stacked0)
            for i, t in enumerate(fresh):
                st = self.tenant(t)
                st.adapters = jax.tree.map(lambda x: x[i], stacked0)
                st.opt_mu = _maybe_slice(opt0.mu, i)
                st.opt_nu = _maybe_slice(opt0.nu, i)
                st.step = 0

        groups: dict[tuple, list] = {}
        for t in order:
            st = self.tenant(t)
            groups.setdefault(
                (st.step, st.epochs_done, st.n_ingested,
                 self._shard_of_partition(st.partition)), []
            ).append(t)

        pending = []
        for (step0, epoch0, spt, shard), group in groups.items():
            ls_epochs, path = self._adapt_group(
                group, spt, shard, epochs=epochs, epoch0=epoch0, step0=step0,
                batch_per_tenant=batch_per_tenant,
            )
            pending.append((group, ls_epochs, path))
        losses: dict[Any, np.ndarray] = {}
        paths = set()
        for group, ls_epochs, path in pending:  # sync AFTER all dispatches
            ls = np.stack([np.asarray(l) for l in ls_epochs])
            paths.add(path)
            for g, t in enumerate(group):
                losses[t] = ls[:, :, g]
        self.counters["adapt/epochs"] += epochs * len(groups)
        return {
            "losses": losses,
            "groups": list(groups.values()),
            "path": "stream" if "stream" in paths else "scan",
        }

    def _adapt_group(
        self, group, spt, shard, *, epochs, epoch0, step0, batch_per_tenant
    ) -> tuple[list, str]:
        """Dispatch one same-(trajectory, shard) group's cached epochs on
        its shard. Returns the per-epoch (steps, N) loss arrays *without*
        host synchronisation — the caller converts after every group is in
        flight."""
        n = len(group)
        device = self._shard_device[shard]
        engine = self.engines[shard]
        states = [self.tenant(t) for t in group]
        stacked = jax.device_put(jax.tree.map(
            lambda *xs: jnp.stack(xs), *[st.adapters for st in states]
        ), device)
        opt_state = jax.device_put(OptState(
            step=jnp.asarray(step0, jnp.int32),
            mu=_maybe_stack([st.opt_mu for st in states]),
            nu=_maybe_stack([st.opt_nu for st in states]),
        ), device)
        # Shadow split (DESIGN.md §13): with a control plane, each tenant's
        # epoch permutes its TRAIN rows only; every holdout_every-th ingested
        # row is reserved for held-out eval. holdout=None is bitwise the
        # historical plan.
        holdout = (
            self.control_cfg.holdout_every if self.control is not None else None
        )
        train_rows, eval_rows = batch_plan.shadow_split(spt, every=holdout)
        do_eval = self.control is not None and eval_rows.size > 0
        bpt = min(batch_per_tenant, train_rows.size)
        row_tenant = FF.fleet_row_tenant(n, bpt)
        partitions = [st.partition for st in states]
        local_parts = [p // self.n_shards for p in partitions]
        # The shard's scope rides the compiled-fn key AND wraps every
        # dispatch below: the fleet-epoch jits trace lazily (first call, and
        # every shape retrace), so the model-axis constrains must be in the
        # ambient context whenever a trace can happen.
        scope = self._scope[shard]
        fn_key = (self.cfg, self.sl, n, self.use_kernel, self._opt_key, scope)
        resident = engine.capacity >= engine.num_samples

        if do_eval:
            eval_idx = jnp.asarray(batch_plan.fleet_eval_index(
                n, spt, holdout_every=holdout, partitions=local_parts,
                partition_stride=self.samples_per_tenant,
            ))
            eval_row_tenant = FF.fleet_row_tenant(n, eval_rows.size)

        if resident:
            epoch_fn = compiled(
                ("fleet_cached_epoch", *fn_key),
                lambda: FF.make_fleet_cached_epoch(
                    self.cfg, self.sl, self.optimizer, n,
                    use_kernel=self.use_kernel, donate=False,
                ),
            )
            if self._export[shard] is None:
                # Id-indexed view for the fused scan; reused across adapt
                # calls until the next ingest writes new rows.
                self._export[shard] = engine.export_skipcache()
            cache = self._export[shard]
        else:
            step_fn = compiled(
                ("fleet_cached_step", *fn_key),
                lambda: jax.jit(FF.make_fleet_cached_step_from_vals(
                    self.cfg, self.sl, self.optimizer, n,
                    use_kernel=self.use_kernel,
                )),
            )
            if do_eval:
                ev_fn = compiled(
                    ("fleet_eval", *fn_key),
                    lambda: FF.make_fleet_eval_loss(
                        self.cfg, self.sl, n, use_kernel=self.use_kernel,
                    ),
                )

        pre_loss = post_loss = None
        if do_eval and not resident:
            # Streaming path: eval rides separate (still backbone-free)
            # dispatches over the engine-read cached rows.
            with scope_ctx(scope):
                pre_loss = ev_fn(
                    self._shard_params[shard], stacked,
                    engine.read(eval_idx), eval_row_tenant,
                )

        all_losses = []
        steps_per_epoch = 0
        for e in range(epochs):
            # The batch plan offsets into the shard-local id space while the
            # RNG stream follows the GLOBAL partition, so a re-sharded (or
            # elastically restored) session replays identical orders.
            idx_mat = batch_plan.fleet_index_matrix(
                epoch0 + e, n, spt, bpt, seed=self.seed,
                partitions=local_parts,
                streams=partitions,
                partition_stride=self.samples_per_tenant,
                holdout_every=holdout,
            )
            steps_per_epoch = idx_mat.shape[0]
            want_pre = do_eval and resident and e == 0
            want_post = do_eval and resident and e == epochs - 1
            if want_pre or want_post:
                # Shadow eval folded into the SAME fused dispatch as the
                # training scan (one jit per (pre, post) flag pair).
                eval_epoch_fn = compiled(
                    ("fleet_cached_epoch_eval", *fn_key, want_pre, want_post),
                    lambda: FF.make_fleet_cached_epoch_eval(
                        self.cfg, self.sl, self.optimizer, n,
                        use_kernel=self.use_kernel,
                        eval_pre=want_pre, eval_post=want_post, donate=False,
                    ),
                )
                with scope_ctx(scope):
                    stacked, opt_state, ls, pre, post = eval_epoch_fn(
                        self._shard_params[shard], stacked, opt_state, cache,
                        jnp.asarray(idx_mat), row_tenant,
                        eval_idx, eval_row_tenant,
                    )
                if want_pre:
                    pre_loss = pre
                if want_post:
                    post_loss = post
            elif resident:
                with scope_ctx(scope):
                    stacked, opt_state, ls = epoch_fn(
                        self._shard_params[shard], stacked, opt_state, cache,
                        jnp.asarray(idx_mat), row_tenant,
                    )
            else:
                with scope_ctx(scope):
                    stacked, opt_state, ls = FF.fleet_cached_epoch_via_engine(
                        step_fn, self._shard_params[shard], stacked, opt_state,
                        engine, idx_mat, row_tenant,
                    )
            all_losses.append(ls)

        if do_eval and not resident:
            with scope_ctx(scope):
                post_loss = ev_fn(
                    self._shard_params[shard], stacked,
                    engine.read(eval_idx), eval_row_tenant,
                )

        # Deterministic from the plan — int(opt_state.step) would sync the
        # device and serialise the per-shard groups we just overlapped.
        step_after = step0 + steps_per_epoch * epochs

        if self.control is None:
            for g, (t, st) in enumerate(zip(group, states)):
                st.adapters = jax.tree.map(lambda x: x[g], stacked)
                st.opt_mu = _maybe_slice(opt_state.mu, g)
                st.opt_nu = _maybe_slice(opt_state.nu, g)
                st.step = step_after
                st.epochs_done = epoch0 + epochs
            self.pool.register_many(group, stacked)
            for t in group:
                self.pool.pin(t)  # in-flight session state: never LRU-evicted
            return all_losses, "scan" if resident else "stream"

        # -- gated write-back (control plane on) -----------------------------
        # The gate needs the eval losses on host NOW, which synchronises this
        # group before the next one dispatches — the (documented, opt-in)
        # price of deciding a write-back on its measured outcome.
        pre_np = None if pre_loss is None else np.asarray(pre_loss)
        post_np = None if post_loss is None else np.asarray(post_loss)
        decisions: dict[Any, str] = {}
        meta: dict[Any, dict] = {}
        for g, t in enumerate(group):
            pre_g = None if pre_np is None else float(pre_np[g])
            post_g = None if post_np is None else float(post_np[g])
            if not self.pool.has(t):
                # First-ever write-back: no served version to protect (and
                # the pool would have no slot to keep serving from).
                dec = "accept"
            else:
                dec = self.control.decide(t, pre_g, post_g)
            decisions[t] = dec
            meta[t] = {"step": step_after, "eval_loss": post_g}
            self.control.record(t, dec, pre=pre_g, post=post_g, step=step_after)
            self.counters[f"control/{dec}"] += 1
        for g, (t, st) in enumerate(zip(group, states)):
            if decisions[t] == "reject":
                # Training state frozen with the served version: the next
                # adapt retrains the same plan from the same state.
                continue
            st.adapters = jax.tree.map(lambda x: x[g], stacked)
            st.opt_mu = _maybe_slice(opt_state.mu, g)
            st.opt_nu = _maybe_slice(opt_state.nu, g)
            st.step = step_after
            st.epochs_done = epoch0 + epochs
        self.pool.register_many(
            group, stacked, gate=decisions.__getitem__, meta=meta,
        )
        for t in group:
            self.pool.pin(t)  # in-flight session state: never LRU-evicted
        # Auto-rollback policy (ControlConfig.auto_rollback_after): a tenant
        # whose last N gated write-backs all failed is presumed to be
        # diverging, not noisy — restore its previous served version (when
        # the slot has archived history; a first-version tenant has nothing
        # older) and reset its optimizer trajectory so the next adapt
        # restarts clean from the adapters it actually serves.
        for g, (t, st) in enumerate(zip(group, states)):
            if decisions[t] == "accept" or not self.control.should_auto_rollback(t):
                continue
            if self.pool.has(t) and self.pool.history_len(t) > 0:
                self.pool.rollback(t)
            st.opt_mu = _maybe_zeros(st.opt_mu)
            st.opt_nu = _maybe_zeros(st.opt_nu)
            st.step = 0
            self.control.record_rollback(t, auto=True)
            self.counters["control/rollbacks"] += 1
            self.counters["control/auto_rollbacks"] += 1
        return all_losses, "scan" if resident else "stream"

    # -- control plane -------------------------------------------------------

    def rollback(self, tenant) -> dict:
        """Serve-plane rollback: restore the tenant's previous adapter
        version into its pool slot — bitwise, from the slot's archived
        storage-layout payload — and bump the pool version so every serve
        slot-index memo (the runtime's ``_idx_cache``, the scheduler's
        refresh key) invalidates. Training state is NOT rewound: quantised
        pools are lossy, so the archived payload cannot reconstruct float
        training state — a rolled-back tenant keeps its optimizer
        trajectory and simply *serves* the older version until a future
        gated adapt produces an acceptable one. Requires a pool built with
        version history (a session with a ``ControlConfig``)."""
        meta = self.pool.rollback(tenant)
        if self.control is not None:
            self.control.record_rollback(tenant)
        self.counters["control/rollbacks"] += 1
        return meta

    def control_metrics(self) -> Optional[dict]:
        """The control plane's JSON-able ledger (None when disabled)."""
        return None if self.control is None else self.control.metrics()

    # -- introspection -------------------------------------------------------

    def _engine_stats(self) -> CacheStats:
        agg = CacheStats()
        for eng in self.engines:
            agg.hbm_hits += eng.stats.hbm_hits
            agg.host_hits += eng.stats.host_hits
            agg.staged_hits += eng.stats.staged_hits
            agg.spills += eng.stats.spills
            agg.writes += eng.stats.writes
        return agg

    def stats(self) -> dict[str, float]:
        out = {f"runtime/{k}": float(v) for k, v in sorted(self.counters.items())}
        eng = self._engine_stats()
        out.update(dict(eng.as_rows()))
        out.update(dict(self.pool.stats.as_rows()))
        out["cache_engine/hbm_hit_rate"] = eng.hbm_hit_rate()
        return out

    # -- checkpoint plane ----------------------------------------------------

    def session_state(self) -> tuple[dict, dict]:
        """(arrays, meta) for ``checkpoint.save_runtime_session``: stacked
        trained adapters + optimizer moments (tenant order in meta), every
        shard's pool data plane + the placement/slot tables, and every
        present skip-cache row in logical layout under *global* ids (the
        shard-local engines are a placement detail; the capture is
        layout-addressed so a restore re-places it). Tenant ids must be
        JSON-serialisable."""
        order = list(self._tenants)
        trained = [t for t in order if self._tenants[t].trained]
        arrays: dict[str, Any] = {}
        if trained:
            sts = [self._tenants[t] for t in trained]
            # Trained tenants may live on different shards: stack on host.
            arrays["adapters"] = jax.tree.map(
                lambda *xs: jnp.asarray(np.stack([np.asarray(x) for x in xs])),
                *[st.adapters for st in sts]
            )
            mu = _maybe_stack_host([st.opt_mu for st in sts])
            nu = _maybe_stack_host([st.opt_nu for st in sts])
            if mu is not None:
                arrays["opt_mu"] = mu
            if nu is not None:
                arrays["opt_nu"] = nu
        arrays["pool"] = self.pool.state_arrays()
        rows: dict[int, dict[str, np.ndarray]] = {}
        for s, eng in enumerate(self.engines):
            pres = sorted(eng._present)
            chunk = max(1, eng.capacity)
            for lo in range(0, len(pres), chunk):
                ids = pres[lo:lo + chunk]
                # One device->host transfer per chunk array, then numpy
                # slicing — never per-row syncs.
                vals = {
                    name: np.asarray(v)
                    for name, v in eng.read(jnp.asarray(ids)).items()
                }
                for pos, lid in enumerate(ids):
                    rows[self._global_id(s, lid)] = {
                        name: v[pos] for name, v in vals.items()
                    }
        present = sorted(rows)
        if present:
            arrays["cache"] = {
                name: jnp.asarray(np.stack([rows[g][name] for g in present]))
                for name in rows[present[0]]
            }
        meta = {
            "tenants": [
                {
                    "id": t,
                    "partition": self._tenants[t].partition,
                    "n_ingested": self._tenants[t].n_ingested,
                    "epochs_done": self._tenants[t].epochs_done,
                    "step": self._tenants[t].step,
                }
                for t in order
            ],
            "trained": trained,
            "pool_table": self.pool.slot_table(),
            "present": present,
            "layout": {"seq": self.seq, "rank": self.sl.rank,
                       "mode": self.sl.mode,
                       "samples_per_tenant": self.samples_per_tenant,
                       "n_shards": self.n_shards,
                       # Restore-compatibility keys: a restore into a
                       # differently-configured session must fail loudly,
                       # not silently reinterpret packed pool bytes.
                       "pool_compress": self.pool.compress,
                       "pool_slots": self.pool.shards[0].n_slots,
                       "max_tenants": self.max_tenants,
                       # Informational (NOT restore-compared): the mesh a
                       # session ran on is a placement detail — an elastic
                       # restart restores the same logical layout onto any
                       # (data, model) mesh with matching logical shards.
                       "mesh_shape": [int(n) for n in np.shape(
                           np.asarray(self.mesh.devices))],
                       "mesh_axes": list(self.mesh.axis_names),
                       "model_parallel": self.model_parallel,
                       "pipeline_stages": self.pipeline_stages},
        }
        if self.control is not None:
            meta["control"] = self.control.state()
        if self._kv_pools:
            arrays["kv_pool"] = {
                str(s): p.state_arrays() for s, p in self._kv_pools.items()
            }
            meta["kv_pool"] = {
                str(s): {
                    **p.state_meta(),
                    "radix": (
                        self._prefix_indexes[s].state()
                        if s in self._prefix_indexes else []
                    ),
                }
                for s, p in self._kv_pools.items()
            }
        return arrays, meta

    def load_session_state(self, arrays: dict, meta: dict) -> None:
        """Restore a ``session_state`` capture into this (fresh) runtime.
        Geometry (config shapes, seq, partition layout, logical shard
        count) must match the saving session — the *mesh* need not: an
        elastic restart restores the same logical layout onto however many
        devices this runtime was built over, and the engines re-place the
        cache rows under THEIR budgets (placement is policy, the bytes are
        identical)."""
        if self._tenants:
            raise RuntimeError("restore requires a fresh runtime")
        lay = meta["layout"]
        saved = (lay["seq"], lay["rank"], lay["mode"],
                 lay["samples_per_tenant"], int(lay.get("n_shards", 1)))
        if saved != (self.seq, self.sl.rank, self.sl.mode,
                     self.samples_per_tenant, self.n_shards):
            raise ValueError(f"session layout {lay} != runtime configuration")
        # Pool layout must match EXACTLY: an int4/nf4 checkpoint restored
        # into an int8 (or float) pool would silently reinterpret packed
        # payload bytes; a different slot count scrambles slot indices.
        # (Keys absent from pre-control checkpoints are not checked.)
        for k, mine in (
            ("pool_compress", self.pool.compress),
            ("pool_slots", self.pool.shards[0].n_slots),
            ("max_tenants", self.max_tenants),
        ):
            if k in lay and lay[k] != mine:
                raise ValueError(
                    f"checkpoint {k}={lay[k]!r} != this runtime's {mine!r}: "
                    "restore requires an identically-configured session"
                )
        if "control" in meta:
            if self.control is None:
                raise ValueError(
                    "checkpoint carries control-plane state (gate ledger, "
                    "quarantine set) but this runtime was built without a "
                    "ControlConfig — restoring would silently drop it"
                )
            self.control.load_state(meta["control"])
        for ent in meta["tenants"]:
            st = TenantState(
                partition=int(ent["partition"]),
                n_ingested=int(ent["n_ingested"]),
                epochs_done=int(ent["epochs_done"]),
                step=int(ent["step"]),
            )
            self._tenants[ent["id"]] = st
            self._free_partitions[
                self._shard_of_partition(st.partition)
            ].remove(st.partition)
        for i, t in enumerate(meta["trained"]):
            st = self._tenants[t]
            st.adapters = jax.tree.map(lambda x: jnp.asarray(x)[i], arrays["adapters"])
            if "opt_mu" in arrays:
                st.opt_mu = jax.tree.map(lambda x: jnp.asarray(x)[i], arrays["opt_mu"])
            if "opt_nu" in arrays:
                st.opt_nu = jax.tree.map(lambda x: jnp.asarray(x)[i], arrays["opt_nu"])
        self.pool.load_state(arrays["pool"], meta["pool_table"])
        present = [int(i) for i in meta["present"]]
        by_shard: dict[int, list[tuple[int, int]]] = {}
        for pos, gid in enumerate(present):
            part = gid // self.samples_per_tenant
            local = (part // self.n_shards) * self.samples_per_tenant + (
                gid % self.samples_per_tenant
            )
            by_shard.setdefault(self._shard_of_partition(part), []).append(
                (pos, local)
            )
        for s, entries in by_shard.items():
            eng = self.engines[s]
            chunk = max(1, eng.capacity)
            for lo in range(0, len(entries), chunk):
                sub = entries[lo:lo + chunk]
                pos_idx = np.asarray([p for p, _ in sub])
                vals = {
                    name: jnp.asarray(np.asarray(arr)[pos_idx])
                    for name, arr in arrays["cache"].items()
                }
                eng.write(jnp.asarray([l for _, l in sub]), vals)
        # Paged prefix cache: pool bytes + radix tree round-trip, with the
        # refcounts recomputed from the restored tree (exactly one ref per
        # node — a fresh session has no in-flight rows, so saved in-flight
        # refs must NOT survive). Geometry mismatches fail loudly inside
        # ``KVBlockPool.load_state``.
        for s_str, pmeta in meta.get("kv_pool", {}).items():
            s = int(s_str)
            pool = self.kv_pool(
                s, block=int(pmeta["block"]), n_blocks=int(pmeta["n_blocks"])
            )
            pool.load_state(arrays["kv_pool"][s_str], pmeta)
            self.prefix_index(s).load_state(pmeta.get("radix", []))


def _maybe_stack(trees: list) -> Optional[Params]:
    if trees[0] is None:
        return None
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _maybe_stack_host(trees: list) -> Optional[Params]:
    """Like ``_maybe_stack`` but via host memory — the checkpoint capture
    stacks tenants from *different* shards, whose leaves are committed to
    different devices."""
    if trees[0] is None:
        return None
    return jax.tree.map(
        lambda *xs: jnp.asarray(np.stack([np.asarray(x) for x in xs])), *trees
    )


def _maybe_slice(tree: Optional[Params], i: int) -> Optional[Params]:
    if tree is None:
        return None
    return jax.tree.map(lambda x: x[i], tree)


def _maybe_zeros(tree: Optional[Params]) -> Optional[Params]:
    if tree is None:
        return None
    return jax.tree.map(jnp.zeros_like, tree)
