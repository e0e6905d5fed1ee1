"""Sharding rules: logical axes -> mesh axes, param/opt/cache spec trees.

Megatron-style tensor parallelism on the ``model`` axis (attention heads,
FFN hidden, experts, vocab), data parallelism on ``("pod", "data")``, and a
simplified ZeRO-1: optimizer moments additionally shard a free weight axis
over ``data``. Long-context decode (batch=1) switches the *sequence* logical
axis onto ``data`` (sequence parallelism over the KV/state caches).

Param specs are derived from tree paths + leaf ranks, so any pytree shaped
like the model zoo's params gets a complete spec tree; unknown leaves fall
back to replication (safe, never wrong, only suboptimal).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import re
from typing import Any, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Params = Any


# ---------------------------------------------------------------------------
# Mesh construction: the one helper every mesh in the repo goes through
# ---------------------------------------------------------------------------


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], *, devices=None
) -> Mesh:
    """Build a mesh of ``shape`` over ``axes``.

    ``devices=None`` takes the process's device list in order (the common
    case); an explicit list pins the grid to those devices — the elastic
    path, where a restart rebuilds the mesh from whatever survived. This is
    the single mesh constructor behind ``launch.mesh``, the fleet/session
    launchers, and ``fault.elastic_remesh``. Every axis is ``Auto`` on both
    branches (``jax.make_mesh`` alone would make them ``Explicit``), so a
    mesh's sharding semantics never depend on which caller built it.
    """
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} != axes {axes}")
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    n = int(np.prod(shape))
    devices = list(devices)
    if len(devices) < n:
        raise ValueError(f"mesh {shape} needs {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes, axis_types=auto)


def session_mesh_layout(mesh: Mesh) -> tuple[int, int, list[list]]:
    """``(n_data, n_model, groups)`` of a session mesh.

    Sessions shard *tenants* over ``("pod", "data")`` and may additionally
    shard the frozen *backbone* over a ``model`` axis (DESIGN.md §14): each
    data shard then owns a model-axis device *group* that holds one
    tensor-parallel backbone replica. ``groups[s]`` is shard ``s``'s device
    list (length ``n_model``); on a data-only mesh every group is a single
    device — the PR 5 committed-replica layout, unchanged.
    """
    data_axes, model_size = [], 1
    for i, (ax, size) in enumerate(zip(mesh.axis_names, mesh.devices.shape)):
        if ax in ("data", "pod"):
            data_axes.append(i)
        elif ax == "model":
            model_size = size
        elif size > 1:
            raise ValueError(
                f"session meshes shard tenants on ('pod', 'data') and the "
                f"backbone on 'model' only; axis {ax!r} has size {size}"
            )
    order = data_axes + [i for i in range(mesh.devices.ndim) if i not in data_axes]
    grid = np.transpose(mesh.devices, order).reshape(-1, model_size)
    return grid.shape[0], model_size, [list(row) for row in grid]


def session_devices(mesh: Mesh) -> list:
    """The data-axis device list of a session mesh, in shard order.

    Mesh-native sessions parallelise the tenant axis over ``("pod",
    "data")``; with a >1 ``model`` axis each data shard is a device *group*
    (one TP backbone replica) and this returns the group anchors — the
    device per shard that host-side bookkeeping (cache tiers, pool stats)
    keys on. ``session_mesh_layout`` exposes the full groups.
    """
    _, _, groups = session_mesh_layout(mesh)
    return [g[0] for g in groups]


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Logical axis name -> mesh axis (or tuple, or None=replicated)."""

    batch: Any = ("pod", "data")
    seq: Any = None                # "data" for long-context decode
    heads: Any = "model"
    vocab: Any = "model"
    ffn: Any = "model"
    expert: Any = "model"
    capacity: Any = None
    d_inner: Any = "model"
    # Batch axis of the (B, chunk, V) logits blocks. Distinct from `batch`:
    # under FSDP the batch axes are re-used for vocab sharding in the loss
    # (keeps d_table local-shard; no full-table all-reduce).
    logits_batch: Any = ("pod", "data")
    # Group dim of the (G, E, C, D) expert buffers. Under 'ep' the batch is
    # grid-sharded for dense layers but must release the 'model' axis to the
    # experts inside MoE blocks (a cheap h-reshard at the block boundary).
    expert_group: Any = ("pod", "data")
    # Layer axis of stacked (L, ...) activation tensors — the skip-cache and
    # the collected block inputs. "model" on session TP meshes: each model
    # shard holds (and skip-sums) its resident blocks' inputs locally and
    # one psum stitches the adapter logits (DESIGN.md §14).
    layers: Any = None

    def resolve(self, mesh_axes: tuple[str, ...], logical: Any) -> Any:
        """Drop mesh axes not present (e.g. 'pod' on the single-pod mesh)."""
        v = getattr(self, logical) if isinstance(logical, str) and hasattr(self, logical) else logical
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in mesh_axes else None
        vs = tuple(a for a in v if a in mesh_axes)
        return vs if vs else None


# ---------------------------------------------------------------------------
# Activation-sharding constraints (contextvar scope; no-op outside)
# ---------------------------------------------------------------------------

_ACTIVE: contextvars.ContextVar[Optional[tuple[Mesh, AxisRules]]] = (
    contextvars.ContextVar("repro_sharding_scope", default=None)
)


@contextlib.contextmanager
def sharding_scope(mesh: Mesh, rules: AxisRules):
    tok = _ACTIVE.set((mesh, rules))
    try:
        yield
    finally:
        _ACTIVE.reset(tok)


@contextlib.contextmanager
def suspend_scope():
    """Clear any active sharding scope for the dynamic extent — for manual
    SPMD regions (``shard_map``) traced under a scoped jit, where the scope's
    auto-constraints would name an axis the region claims as manual."""
    tok = _ACTIVE.set(None)
    try:
        yield
    finally:
        _ACTIVE.reset(tok)


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """with_sharding_constraint against the active scope (no-op if none).

    Axes whose dimension is smaller than the mesh-axis size are left
    replicated (e.g. 8 KV heads on a 16-way model axis).
    """
    active = _ACTIVE.get()
    if active is None:
        return x
    mesh, rules = active
    parts = []
    for dim, a in zip(x.shape, logical_axes):
        r = rules.resolve(mesh.axis_names, a)
        if r is not None and dim % _axis_size(mesh, r) != 0:
            r = None
        parts.append(r)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


# Session TP rules: within one data shard's model group the *rows* are not
# sharded (the data axis lives across groups, not inside the jit), so every
# batch-like logical axis replicates and the tensor axes follow Megatron.
SESSION_TP_RULES = AxisRules(
    batch=None, seq=None, heads="model", vocab="model", ffn="model",
    expert="model", capacity=None, d_inner="model", logits_batch=None,
    expert_group=None, layers="model",
)


@dataclasses.dataclass(frozen=True)
class ShardScope:
    """Hashable (mesh, rules) pair a compiled-fn factory can close over.

    The ``sharding_scope`` contextvar is read at TRACE time, so any cached
    jit whose body should emit ``constrain`` ops must key its cache entry on
    the scope — this dataclass is that key (``Mesh`` and ``AxisRules`` are
    both hashable) and ``ctx()`` is the trace-time activation.
    """

    mesh: Mesh
    rules: AxisRules = SESSION_TP_RULES

    def ctx(self):
        return sharding_scope(self.mesh, self.rules)


def scope_ctx(scope: Optional[ShardScope]):
    """``scope.ctx()`` or a no-op context — for fns compiled both ways."""
    return scope.ctx() if scope is not None else contextlib.nullcontext()


def shard_submesh(mesh: Mesh, shard: int) -> Mesh:
    """Shard ``shard``'s model-axis group as its own 1-D ``("model",)``
    mesh — the device set every dispatch of that data shard runs on."""
    _, _, groups = session_mesh_layout(mesh)
    return Mesh(np.asarray(groups[shard]), ("model",))


def shard_backbone(params: Params, submesh: Mesh) -> Params:
    """One TP-sharded backbone replica committed to a shard's model group
    (the >1-model-axis counterpart of ``replicate_backbone``): params whose
    rule resolves shard over ``model``, the rest replicate over the group.
    Committed inputs pin every downstream jit to the group's device set,
    exactly like the single-device committed replicas do today."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
    )
    return jax.device_put(params, named(submesh, param_specs(shapes, submesh)))


# ---------------------------------------------------------------------------
# Param spec derivation
# ---------------------------------------------------------------------------


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_size(mesh: Mesh, axis: Any) -> int:
    axes = (axis,) if isinstance(axis, str) else axis
    size = 1
    for a in axes:
        size *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return size


def _divisible(dim: int, mesh: Mesh, axis: Any) -> bool:
    """jit argument shardings must divide evenly (unlike intermediate
    constraints, which GSPMD pads); non-divisible dims fall back to the
    next rule or replication."""
    if axis is None:
        return True
    return dim % _axis_size(mesh, axis) == 0


def _param_spec_for(path: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Spec for one leaf. ``shape`` excludes any leading periods-stack axis."""
    nd = len(shape)

    def m(ax_idx: int, axis="model") -> Any:
        return axis if _divisible(shape[ax_idx], mesh, axis) else None

    # Embedding / untied head: vocab-sharded (keeps logits vocab-sharded).
    if re.search(r"(embed|head)/table$", path):
        return P(m(0), None)
    # Attention: shard heads; if the head count doesn't divide the model
    # axis (musicgen 24H, paligemma MQA), fall back to sharding d_model
    # (contraction dim -> partial sums + all-reduce, Megatron row-parallel).
    if re.search(r"attn/w[qkv]$", path):
        if m(1):
            return P(None, "model", None)
        return P(m(0), None, None)
    if re.search(r"attn/wo$", path):
        if m(0):
            return P("model", None, None)
        return P(None, None, m(2))
    # MoE experts (rank 3: E, D, F / E, F, D): shard experts (EP); if the
    # expert count doesn't divide (qwen 60e on 16), shard the expert FFN
    # hidden dim instead (TP inside each expert).
    if "/moe/" in path:
        if path.endswith("router"):
            return P(None, None)
        if nd == 3:
            if m(0):
                return P("model", None, None)
            if path.endswith("w_down"):
                return P(None, m(1), None)
            return P(None, None, m(2))
        # shared expert (rank-2 FFN weights)
        if re.search(r"w_(gate|up)$", path):
            return P(None, m(1))
        if path.endswith("w_down"):
            return P(m(0), None)
        return P(*([None] * nd))
    # Dense FFN.
    if re.search(r"ffn/w_(gate|up)$", path):
        return P(None, m(1))
    if re.search(r"ffn/w_down$", path):
        return P(m(0), None)
    # Mamba.
    if "/mamba/" in path:
        if path.endswith(("in_proj",)):
            return P(None, m(1))
        if path.endswith(("x_proj", "out_proj", "a_log")):
            return P(m(0), None)
        if path.endswith("dt_proj"):
            return P(None, m(1))
        if path.endswith("conv_w"):
            return P(None, m(1))
        if path.endswith(("dt_bias", "d_skip")):
            return P(m(0))
        return P(*([None] * nd))
    # mLSTM.
    if "/mlstm/" in path:
        if path.endswith(("up_proj", "conv_w")):
            return P(None, m(1))
        if path.endswith(("wq", "wk", "wv")):
            return P(m(0), None, None)
        if path.endswith(("w_i", "w_f")):
            return P(m(0), None)
        if path.endswith("down_proj"):
            return P(m(0), None)
        return P(*([None] * nd))
    # sLSTM: small dense recurrence -> replicate.
    # Norms, biases, everything else: replicate.
    return P(*([None] * nd))


def param_specs(params_shape: Params, mesh: Mesh) -> Params:
    """Spec tree matching a params (or ShapeDtypeStruct) tree."""

    def leaf_spec(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        if "periods" in pstr and shape:
            # Leading n_periods stack axis is never sharded.
            inner = _param_spec_for(pstr, shape[1:], mesh)
            return P(None, *inner)
        return _param_spec_for(pstr, shape, mesh)

    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


def session_param_specs(params_shape: Params, mesh: Mesh) -> Params:
    """Backbone placement for a session mesh, derived from the same rule
    table as the pretraining path: a mesh carrying a >1 ``model`` axis gets
    the Megatron ``param_specs``; on a data-only session mesh every rule
    resolves to replication — the *adapters, moments and cache partitions*
    carry the data axis (by tenant), never the frozen backbone."""
    if "model" in mesh.axis_names and _axis_size(mesh, "model") > 1:
        return param_specs(params_shape, mesh)
    return jax.tree.map(lambda x: P(*([None] * len(x.shape))), params_shape)


def specs_all_replicated(specs: Params) -> bool:
    return all(
        all(part is None for part in spec)
        for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    )


def replicate_backbone(params: Params, devices) -> list[Params]:
    """Per-device committed replicas of the frozen backbone — the physical
    realisation of all-replicated ``session_param_specs`` that keeps every
    per-shard dispatch device-local (a committed-input jit runs entirely on
    its shard; a GSPMD-replicated array would force one SPMD program)."""
    return [jax.device_put(params, d) for d in devices]


def zero1_specs(params_shape: Params, specs: Params, mesh: Mesh) -> Params:
    """Optimizer-moment specs: like param specs but additionally shard the
    first still-replicated axis over 'data' when divisible (ZeRO-1)."""

    def upgrade(leaf, spec):
        shape = tuple(leaf.shape)
        parts = list(spec) + [None] * (len(shape) - len(spec))

        def uses_data(p):
            return p == "data" or (isinstance(p, tuple) and "data" in p)

        if any(uses_data(p) for p in parts):
            return P(*parts)  # already data-sharded (idempotent)
        data_size = _axis_size(mesh, "data")
        for i, (dim, pspec) in enumerate(zip(shape, parts)):
            if pspec is None and dim % data_size == 0 and dim >= 128:
                parts[i] = "data"
                break
        return P(*parts)

    return jax.tree.map(upgrade, params_shape, specs, is_leaf=lambda x: isinstance(x, P))


def spec_shards(shape: tuple[int, ...], spec: P, mesh: Mesh) -> int:
    total = 1
    for part in spec:
        if part is not None:
            total *= _axis_size(mesh, part)
    return total


def per_device_bytes(params_shape: Params, specs: Params, mesh: Mesh) -> float:
    flat = jax.tree.leaves(params_shape)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    total = 0.0
    for leaf, spec in zip(flat, flat_s):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n * jnp.dtype(leaf.dtype).itemsize / spec_shards(leaf.shape, spec, mesh)
    return total


def fsdp_param_specs(params_shape: Params, mesh: Mesh) -> Params:
    """Fully-sharded weights: every large leaf shards its first axis that
    divides the full (data x model) device grid; falls back to 'data'-only,
    then replication. Batch shards over the same grid (per-device batch ~1),
    so layers see *local* activations and weights all-gather per use —
    traffic ~ 3 x param bytes per step instead of ~ L x activation bytes."""
    grid = tuple(a for a in ("data", "model") if a in mesh.axis_names)

    def leaf_spec(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        start = 1 if ("periods" in pstr and shape) else 0
        if re.search(r"(embed|head)/table$", pstr):
            # (V, D): prefer vocab sharded over the whole grid (matches the
            # grid-vocab loss sharding when V divides); else V@model, D@data.
            if shape[0] % _axis_size(mesh, grid) == 0:
                return P(grid, None)
            v_ok = shape[0] % _axis_size(mesh, "model") == 0
            d_ok = shape[1] % _axis_size(mesh, "data") == 0
            return P("model" if v_ok else None, "data" if d_ok else None)
        n = 1
        for d in shape:
            n *= d
        parts = [None] * len(shape)
        if n >= (1 << 16):
            for i in range(start, len(shape)):
                if shape[i] % _axis_size(mesh, grid) == 0:
                    parts[i] = grid
                    break
                if shape[i] % _axis_size(mesh, "data") == 0:
                    parts[i] = "data"
                    break
        return P(*parts)

    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


FSDP_RULES_KW = dict(
    batch=("data", "model"),  # per-device batch ~1; pod stays pure DP
    heads=None,
    vocab="model",            # loss logits: batch@data x vocab@model —
    ffn=None,                 # d_table never materialises unsharded
    expert=None,
    capacity=None,            # group dim already carries the batch shard
    d_inner=None,
    logits_batch=("data",),
    expert_group=("data", "model"),
)

# 'ep': FSDP for the dense path (grid-sharded batch, no per-layer h
# all-reduce) + expert parallelism for MoE blocks (experts on 'model',
# expert buffers grouped on 'data') — the batch reshards cheaply at MoE
# boundaries instead of paying 2 all-reduces per layer.
EP_RULES_KW = dict(
    batch=("data", "model"),
    heads=None,
    vocab="model",
    ffn=None,
    expert="model",
    capacity=None,
    d_inner=None,
    logits_batch=("data",),
    expert_group=("pod", "data"),
)


def ep_param_specs(params_shape: Params, mesh: Mesh) -> Params:
    """'ep' strategy weights: MoE expert tensors (rank 3 under /moe/) shard
    E over 'model' and their widest remaining axis over 'data'; everything
    else is FSDP-sharded over the grid."""
    base = fsdp_param_specs(params_shape, mesh)

    def leaf_spec(path, leaf, spec):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        start = 1 if ("periods" in pstr and shape) else 0
        if "/moe/" in pstr and len(shape) - start == 3:
            e_ok = shape[start] % _axis_size(mesh, "model") == 0
            parts = [None] * len(shape)
            if e_ok:
                parts[start] = "model"
            for i in range(start + 1, len(shape)):
                if shape[i] % _axis_size(mesh, "data") == 0:
                    parts[i] = "data"
                    break
            return P(*parts)
        return spec

    return jax.tree_util.tree_map_with_path(
        leaf_spec, params_shape, base
    )


def maybe_fsdp_specs(
    params_shape: Params, specs: Params, mesh: Mesh, *, threshold_bytes: float = 8e9
) -> tuple[Params, bool]:
    """If the TP-sharded weights still exceed ``threshold_bytes`` per device
    (jamba-398B on a 16-way model axis), additionally shard every large leaf
    over 'data' (FSDP: weights all-gather per layer). Returns (specs, applied).
    """
    if per_device_bytes(params_shape, specs, mesh) <= threshold_bytes:
        return specs, False
    return zero1_specs(params_shape, specs, mesh), True


def named(mesh: Mesh, spec_tree: Params) -> Params:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_spec(mesh: Mesh, rules: AxisRules, *trailing) -> P:
    return P(rules.resolve(mesh.axis_names, "batch"), *trailing)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
