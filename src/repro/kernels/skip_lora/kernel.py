"""Pallas TPU kernels for the fused Skip-LoRA aggregation.

Shapes: x (L, M, D) cached activations (M = batch*seq rows), a (L, D, R),
b (L, R, D), out (M, D). R is the LoRA rank (4..64), far below the 128x128
MXU tile — so the win is not MXU utilisation on the tiny contractions but
HBM traffic: each x tile is read exactly once across all L layers and the
(M, D) output is written once, instead of L round-trips.

Forward grid (m_tiles, L): the layer axis is the *inner, arbitrary* axis so
the fp32 output block stays resident in VMEM while layers accumulate into
it (out index_map ignores l -> block revisited, initialised at l == 0).

Backward grid (L, m_tiles): per-layer gA (D, R) / gB (R, D) blocks stay
resident while row tiles stream (accumulated over m, initialised at m == 0).

Grouped (multi-tenant serving) variants take a stacked adapter *pool*
(N, L, D, R) plus a per-row-tile slot index delivered by scalar prefetch:
rows are pre-grouped by adapter so each tile gathers exactly one (A, B)
layer block from the pool per grid step (BGMV-style). The int8 grouped
variant keeps the pool int8 in HBM and dequantises gathered blocks in VMEM.

Scales enter every kernel as columns — pool scales (N, L, rows, 1), cache
row scales (L, M, 1) — reshaped by the wrappers below. Mosaic tiles a
block's last two dims by (8, 128) unless they equal the array's, so a
(1, 1, rows) block of an (N, L, rows) array is refused while (1, 1, rows, 1)
is not, and a column broadcasts over the payload's lanes with no relayout.

VMEM budget per step (bf16, TM=128, D=8192 worst case among assigned archs):
x tile 2 MB + fp32 out tile 4 MB + A/B/z < 1.5 MB << 16 MB/core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.skip_lora import quant as _Q

#: Default row-tile size (MXU-aligned). Every kernel below takes ``tm`` as a
#: static parameter; this constant is only the untuned fallback — the
#: autotune harness (``kernels.autotune``) measures per-config winners and
#: threads them through ``ops`` (``ops.set_default_tile``). Valid tiles are
#: bounded below by the dtype's minimum sublane count on TPU (f32 8, bf16 16,
#: int8/uint8 32 — see ``autotune.tile_candidates``).
TM = 128


def _grouped_grid(grid_order: str, m_tiles: int, lnum: int):
    """Grid + index-map convention for the grouped forwards.

    ``"ml"`` (default): rows outer, layers inner — the fp32 out block stays
    VMEM-resident while layers accumulate (one write-back per row tile).
    ``"lm"``: layers outer, rows inner — each (A, B) layer block is gathered
    once per (slot, layer) instead of once per (tile, layer), at the price
    of revisiting out blocks across the outer axis (flush + re-fetch per
    layer). Which wins is a bandwidth-vs-revisit trade the autotuner
    measures per config. Returns (grid, wrap, l_axis, semantics) where
    ``wrap`` lifts an index map written in (mi, li, g) convention into the
    grid's argument order."""
    if grid_order == "ml":
        return (
            (m_tiles, lnum),
            lambda f: (lambda mi, li, g: f(mi, li, g)),
            1,
            ("parallel", "arbitrary"),
        )
    if grid_order == "lm":
        # Out blocks are revisited across the OUTER axis, so neither axis
        # may be reordered: both arbitrary.
        return (
            (lnum, m_tiles),
            lambda f: (lambda li, mi, g: f(mi, li, g)),
            0,
            ("arbitrary", "arbitrary"),
        )
    raise ValueError(f"unknown grid_order {grid_order!r} (want 'ml' or 'lm')")


# ---------------------------------------------------------------------------
# Forward: out[m, :] = sum_l x[l, m, :] @ a[l] @ b[l]
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, a_ref, b_ref, o_ref):
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]  # (TM, D)
    a = a_ref[0].astype(x.dtype)  # (D, R)
    b = b_ref[0].astype(x.dtype)  # (R, D)
    z = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)
    o_ref[...] += jnp.dot(z, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def skip_lora_fwd(
    x: jax.Array, a: jax.Array, b: jax.Array, *, tm: int = TM, interpret: bool = False
) -> jax.Array:
    lnum, m, d = x.shape
    r = a.shape[-1]
    assert m % tm == 0, f"rows {m} must be padded to a multiple of {tm}"
    grid = (m // tm, lnum)
    out = pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d), lambda mi, li: (li, mi, 0)),
            pl.BlockSpec((1, d, r), lambda mi, li: (li, 0, 0)),
            pl.BlockSpec((1, r, d), lambda mi, li: (li, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda mi, li: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, a, b)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Backward: gA[l] = x[l]^T (g b[l]^T);  gB[l] = (x[l] a[l])^T g
# ---------------------------------------------------------------------------


def _bwd_kernel(x_ref, a_ref, b_ref, g_ref, ga_ref, gb_ref):
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        ga_ref[...] = jnp.zeros_like(ga_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    x = x_ref[0]                    # (TM, D)
    g = g_ref[...]                  # (TM, D)
    a = a_ref[0].astype(x.dtype)    # (D, R)
    b = b_ref[0].astype(x.dtype)    # (R, D)
    z = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)   # (TM, R)
    gz = jnp.dot(g, b.T, preferred_element_type=jnp.float32).astype(x.dtype)  # (TM, R)
    ga_ref[0] += jnp.dot(x.T, gz, preferred_element_type=jnp.float32)
    gb_ref[0] += jnp.dot(z.T, g, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def skip_lora_bwd(
    x: jax.Array, a: jax.Array, b: jax.Array, g: jax.Array, *, tm: int = TM,
    interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    lnum, m, d = x.shape
    r = a.shape[-1]
    assert m % tm == 0
    grid = (lnum, m // tm)
    ga, gb = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d), lambda li, mi: (li, mi, 0)),
            pl.BlockSpec((1, d, r), lambda li, mi: (li, 0, 0)),
            pl.BlockSpec((1, r, d), lambda li, mi: (li, 0, 0)),
            pl.BlockSpec((tm, d), lambda li, mi: (mi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d, r), lambda li, mi: (li, 0, 0)),
            pl.BlockSpec((1, r, d), lambda li, mi: (li, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lnum, d, r), jnp.float32),
            jax.ShapeDtypeStruct((lnum, r, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, a, b, g)
    return ga, gb


# ---------------------------------------------------------------------------
# int8 forward: x[l] = q[l] * scale[l][:, None], dequant fused into the
# A-projection so the int8 cache never round-trips through HBM as bf16.
# ---------------------------------------------------------------------------


def _grouped_fwd_kernel(l_axis, g_ref, x_ref, a_ref, b_ref, o_ref):
    del g_ref  # consumed by the index_maps; the body sees gathered blocks
    l = pl.program_id(l_axis)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                        # (TM, D)
    a = a_ref[0, 0].astype(x.dtype)     # (D, R)
    b = b_ref[0, 0].astype(x.dtype)     # (R, D)
    z = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)
    o_ref[...] += jnp.dot(z, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "grid_order", "interpret"))
def skip_lora_grouped_fwd(
    x: jax.Array,            # (L, M, D) rows pre-grouped by adapter
    a_pool: jax.Array,       # (N, L, D, R) stacked adapter pool
    b_pool: jax.Array,       # (N, L, R, D)
    tile_adapter: jax.Array,  # (M // tm,) int32 adapter slot per row tile
    *,
    tm: int = TM,
    grid_order: str = "ml",
    interpret: bool = False,
) -> jax.Array:
    """BGMV-style grouped forward: out[m] = sum_l x[l,m] @ A[g,l] @ B[g,l]
    where g = tile_adapter[m // tm]. The caller groups rows so every row
    tile maps to exactly ONE adapter slot; the tile->slot map rides in as a
    scalar-prefetch operand so each (A, B) layer block is gathered from the
    pool into VMEM once per tile — HBM traffic is the *active* adapters'
    blocks, never the whole pool (DESIGN.md §6). ``tm``/``grid_order`` are
    the autotuned tile parameters (``kernels.autotune``)."""
    lnum, m, d = x.shape
    n, _, _, r = a_pool.shape
    assert m % tm == 0, f"rows {m} must be padded to a multiple of {tm}"
    grid, wrap, l_axis, semantics = _grouped_grid(grid_order, m // tm, lnum)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d), wrap(lambda mi, li, g: (li, mi, 0))),
            pl.BlockSpec((1, 1, d, r), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
            pl.BlockSpec((1, 1, r, d), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
        ],
        out_specs=pl.BlockSpec((tm, d), wrap(lambda mi, li, g: (mi, 0))),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_fwd_kernel, l_axis),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(tile_adapter, x, a_pool, b_pool)
    return out.astype(x.dtype)


def _grouped_fwd_int8_kernel(l_axis, g_ref, x_ref, qa_ref, sa_ref, qb_ref, sb_ref, o_ref):
    del g_ref
    l = pl.program_id(l_axis)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                                             # (TM, D)
    a = (qa_ref[0, 0].astype(jnp.float32) * sa_ref[0, 0]).astype(x.dtype)
    b = (qb_ref[0, 0].astype(jnp.float32) * sb_ref[0, 0]).astype(x.dtype)
    z = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)
    o_ref[...] += jnp.dot(z, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "grid_order", "interpret"))
def skip_lora_grouped_fwd_int8(
    x: jax.Array,             # (L, M, D) rows pre-grouped by adapter
    qa: jax.Array,            # (N, L, D, R) int8 pool payload
    sa: jax.Array,            # (N, L, D) fp32 rowwise scales for A
    qb: jax.Array,            # (N, L, R, D) int8
    sb: jax.Array,            # (N, L, R) fp32 rowwise scales for B
    tile_adapter: jax.Array,  # (M // tm,) int32
    *,
    tm: int = TM,
    grid_order: str = "ml",
    interpret: bool = False,
) -> jax.Array:
    """Grouped forward over an int8-compressed adapter pool. The pool stays
    int8 in HBM (4x the resident tenants of bf16); dequant happens on the
    gathered per-tile blocks in VMEM, so the full-precision adapters are
    never materialised outside the kernel."""
    lnum, m, d = x.shape
    n, _, _, r = qa.shape
    sa, sb = sa[..., None], sb[..., None]  # columns: see module docstring
    assert m % tm == 0
    grid, wrap, l_axis, semantics = _grouped_grid(grid_order, m // tm, lnum)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d), wrap(lambda mi, li, g: (li, mi, 0))),
            pl.BlockSpec((1, 1, d, r), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
            pl.BlockSpec((1, 1, d, 1), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
            pl.BlockSpec((1, 1, r, d), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
            pl.BlockSpec((1, 1, r, 1), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
        ],
        out_specs=pl.BlockSpec((tm, d), wrap(lambda mi, li, g: (mi, 0))),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_fwd_int8_kernel, l_axis),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(tile_adapter, x, qa, sa, qb, sb)
    return out.astype(x.dtype)


def _codebook_lookup(code_ref, nib: jax.Array) -> jax.Array:
    """``code[nib]`` as a 16-way select over the SMEM codebook: Mosaic
    lowers only 2-D gathers, and a select picks each level exactly."""
    out = jnp.zeros(nib.shape, jnp.float32)
    for k in range(16):
        out = jnp.where(nib == k, code_ref[0, k], out)
    return out


def _grouped_fwd_q4_kernel(
    l_axis, g_ref, x_ref, qa_ref, sa_ref, qb_ref, sb_ref, code_ref, o_ref
):
    del g_ref
    l = pl.program_id(l_axis)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                                             # (TM, D)
    # Unpack nibbles + codebook-dequant the gathered blocks in VMEM: the
    # pool payload crosses HBM packed (two 4-bit indices per byte). The
    # unpack runs on int32: Mosaic cannot relayout a uint8 interleave.
    a_nib = _Q.unpack_nibbles(qa_ref[0, 0].astype(jnp.int32))  # (D, R)
    b_nib = _Q.unpack_nibbles(qb_ref[0, 0].astype(jnp.int32))  # (R, D)
    a = (_codebook_lookup(code_ref, a_nib) * sa_ref[0, 0]).astype(x.dtype)
    b = (_codebook_lookup(code_ref, b_nib) * sb_ref[0, 0]).astype(x.dtype)
    z = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)
    o_ref[...] += jnp.dot(z, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "grid_order", "interpret"))
def skip_lora_grouped_fwd_q4(
    x: jax.Array,             # (L, M, D) rows pre-grouped by adapter
    qa: jax.Array,            # (N, L, D, R // 2) packed 4-bit pool payload
    sa: jax.Array,            # (N, L, D) fp32 rowwise absmax scales for A
    qb: jax.Array,            # (N, L, R, D // 2) packed 4-bit
    sb: jax.Array,            # (N, L, R) fp32 rowwise absmax scales for B
    code: jax.Array,          # (1, 16) fp32 codebook (int4 or nf4 levels)
    tile_adapter: jax.Array,  # (M // tm,) int32
    *,
    tm: int = TM,
    grid_order: str = "ml",
    interpret: bool = False,
) -> jax.Array:
    """Grouped forward over a packed-4-bit adapter pool (int4 or nf4 — the
    codebook decides, see ``kernels.skip_lora.quant``). The payload stays
    packed in HBM (8x the resident tenants of bf16, 2x int8); nibble unpack
    + codebook dequant happen on the gathered per-tile blocks in VMEM."""
    lnum, m, d = x.shape
    n, _, _, rp = qa.shape
    r = 2 * rp
    sa, sb = sa[..., None], sb[..., None]  # columns: see module docstring
    assert m % tm == 0
    grid, wrap, l_axis, semantics = _grouped_grid(grid_order, m // tm, lnum)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d), wrap(lambda mi, li, g: (li, mi, 0))),
            pl.BlockSpec((1, 1, d, rp), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
            pl.BlockSpec((1, 1, d, 1), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
            pl.BlockSpec((1, 1, r, d // 2), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
            pl.BlockSpec((1, 1, r, 1), wrap(lambda mi, li, g: (g[mi], li, 0, 0))),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((tm, d), wrap(lambda mi, li, g: (mi, 0))),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_fwd_q4_kernel, l_axis),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(tile_adapter, x, qa, sa, qb, sb, code)
    return out.astype(x.dtype)


def _grouped_fwd_actint8_kernel(g_ref, q_ref, s_ref, a_ref, b_ref, o_ref):
    del g_ref
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0].astype(jnp.float32)              # (TM, D)
    s = s_ref[0]                                  # (TM, 1) fp32
    x = (q * s).astype(jnp.bfloat16)
    a = a_ref[0, 0].astype(jnp.bfloat16)          # (D, R) gathered from pool
    b = b_ref[0, 0].astype(jnp.bfloat16)          # (R, D)
    z = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    o_ref[...] += jnp.dot(z, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def skip_lora_grouped_fwd_actint8(
    q: jax.Array,             # (L, M, D) int8 rows pre-grouped by adapter
    scale: jax.Array,         # (L, M) fp32 per-row dequant scales
    a_pool: jax.Array,        # (N, L, D, R) float adapter pool
    b_pool: jax.Array,        # (N, L, R, D)
    tile_adapter: jax.Array,  # (M // tm,) int32
    *,
    tm: int = TM,
    interpret: bool = False,
) -> jax.Array:
    """Grouped forward over an int8-compressed *activation* cache (the
    training-side mirror of ``skip_lora_grouped_fwd_int8``, whose int8 side
    is the pool). Rows stay int8 in HBM; dequant is fused into the
    A-projection per gathered tile, so the raw cache payload feeds the fleet
    trainer without ever materialising bf16 activations outside the kernel."""
    lnum, m, d = q.shape
    n, _, _, r = a_pool.shape
    assert m % tm == 0
    grid = (m // tm, lnum)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d), lambda mi, li, g: (li, mi, 0)),
            pl.BlockSpec((1, tm, 1), lambda mi, li, g: (li, mi, 0)),
            pl.BlockSpec((1, 1, d, r), lambda mi, li, g: (g[mi], li, 0, 0)),
            pl.BlockSpec((1, 1, r, d), lambda mi, li, g: (g[mi], li, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda mi, li, g: (mi, 0)),
    )
    out = pl.pallas_call(
        _grouped_fwd_actint8_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(tile_adapter, q, scale[..., None], a_pool, b_pool)
    return out.astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Grouped backward: per-adapter gA[n] / gB[n] via the same sort-by-slot
# segment tiling as the forward. Rows are pre-grouped so ``tile_adapter`` is
# non-decreasing; for a fixed layer each (slot, layer) output block is
# therefore visited in exactly ONE contiguous run of row tiles — it stays
# VMEM-resident across the run (zero-initialised on first visit, detected by
# comparing the tile's slot with its predecessor's) and flushes once when
# the slot changes. Slots with no rows are never visited; the ops wrapper
# masks their (uninitialised) blocks to zero.
# ---------------------------------------------------------------------------


def _grouped_bwd_kernel(g_ref, x_ref, a_ref, b_ref, gy_ref, ga_ref, gb_ref):
    mi = pl.program_id(1)
    cur = g_ref[mi]
    prev = g_ref[jnp.maximum(mi - 1, 0)]
    first_visit = jnp.logical_or(mi == 0, cur != prev)

    @pl.when(first_visit)
    def _init():
        ga_ref[...] = jnp.zeros_like(ga_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    x = x_ref[0]                        # (TM, D)
    gy = gy_ref[...].astype(x.dtype)    # (TM, D)
    a = a_ref[0, 0].astype(x.dtype)     # (D, R)
    b = b_ref[0, 0].astype(x.dtype)     # (R, D)
    z = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)     # (TM, R)
    gz = jnp.dot(gy, b.T, preferred_element_type=jnp.float32).astype(x.dtype)  # (TM, R)
    ga_ref[0, 0] += jnp.dot(x.T, gz, preferred_element_type=jnp.float32)
    gb_ref[0, 0] += jnp.dot(z.T, gy, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def skip_lora_grouped_bwd(
    x: jax.Array,             # (L, M, D) rows pre-grouped by adapter
    a_pool: jax.Array,        # (N, L, D, R)
    b_pool: jax.Array,        # (N, L, R, D)
    g: jax.Array,             # (M, D) output cotangent, grouped row layout
    tile_adapter: jax.Array,  # (M // tm,) int32, non-decreasing
    *,
    tm: int = TM,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fleet backward: gA[n,l] = sum_{m in group n} x[l,m]^T (g[m] B[n,l]^T),
    gB[n,l] = (x[l,m] A[n,l])^T g[m]. Grid (L, m_tiles) with the row axis
    inner so each per-(slot, layer) gradient block accumulates VMEM-resident
    over its contiguous tile run (rows sorted by slot). Empty slots are never
    visited — callers mask them (``ops._grouped_rows_train``)."""
    lnum, m, d = x.shape
    n, _, _, r = a_pool.shape
    assert m % tm == 0
    grid = (lnum, m // tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d), lambda li, mi, g: (li, mi, 0)),
            pl.BlockSpec((1, 1, d, r), lambda li, mi, g: (g[mi], li, 0, 0)),
            pl.BlockSpec((1, 1, r, d), lambda li, mi, g: (g[mi], li, 0, 0)),
            pl.BlockSpec((tm, d), lambda li, mi, g: (mi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, d, r), lambda li, mi, g: (g[mi], li, 0, 0)),
            pl.BlockSpec((1, 1, r, d), lambda li, mi, g: (g[mi], li, 0, 0)),
        ],
    )
    ga, gb = pl.pallas_call(
        _grouped_bwd_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, lnum, d, r), jnp.float32),
            jax.ShapeDtypeStruct((n, lnum, r, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(tile_adapter, x, a_pool, b_pool, g)
    return ga, gb


def _fwd_int8_kernel(q_ref, s_ref, a_ref, b_ref, o_ref):
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0].astype(jnp.float32)          # (TM, D)
    s = s_ref[0]                              # (TM, 1) fp32
    x = (q * s).astype(jnp.bfloat16)
    a = a_ref[0].astype(jnp.bfloat16)
    b = b_ref[0].astype(jnp.bfloat16)
    z = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    o_ref[...] += jnp.dot(z, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def skip_lora_fwd_int8(
    q: jax.Array, scale: jax.Array, a: jax.Array, b: jax.Array, *, tm: int = TM,
    interpret: bool = False
) -> jax.Array:
    lnum, m, d = q.shape
    r = a.shape[-1]
    assert m % tm == 0
    grid = (m // tm, lnum)
    out = pl.pallas_call(
        _fwd_int8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d), lambda mi, li: (li, mi, 0)),
            pl.BlockSpec((1, tm, 1), lambda mi, li: (li, mi, 0)),
            pl.BlockSpec((1, d, r), lambda mi, li: (li, 0, 0)),
            pl.BlockSpec((1, r, d), lambda mi, li: (li, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda mi, li: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, scale[..., None], a, b)
    return out.astype(jnp.bfloat16)
