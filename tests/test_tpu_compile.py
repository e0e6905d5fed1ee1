"""Every Pallas kernel compiles for a described TPU v5e at stablelm-1.6b width.

Nothing here runs on a chip: each test lowers one kernel for a v5e chip
that is described, not attached, and compiles it with the TPU compiler
installed alongside JAX. That finds what interpret mode cannot — block
shapes Mosaic refuses, ops it cannot lower, VMEM overflows — at no chip
time. Widths: 24 layers, d 2048, rank 8, a pool of 8 slots, 256 rows, bf16
activations, 64-wide heads.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the test workers all import
this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attn import kernel as FK
from repro.kernels.flash_attn import paged as FP
from repro.kernels.skip_lora import kernel as K

L, D, R, N, M, TM = 24, 2048, 8, 8, 256, 128
HEADS, HD, S = 32, 64, 256
BLOCK, N_BLOCKS = 8, 64
BF16, F32, I8, U8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.uint8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one; keep the cache out of it.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to compile with
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lowered(name: str, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    tiles = s((M // TM,), I32)
    if name == "skip_lora_fwd":
        return K.skip_lora_fwd.lower(s((L, M, D), BF16), s((L, D, R), F32),
                                     s((L, R, D), F32))
    if name == "skip_lora_bwd":
        return K.skip_lora_bwd.lower(s((L, M, D), BF16), s((L, D, R), F32),
                                     s((L, R, D), F32), s((M, D), BF16))
    if name in ("skip_lora_grouped_fwd_ml", "skip_lora_grouped_fwd_lm"):
        return K.skip_lora_grouped_fwd.lower(
            s((L, M, D), BF16), s((N, L, D, R), F32), s((N, L, R, D), F32),
            tiles, grid_order=name[-2:],
        )
    if name == "skip_lora_grouped_bwd":
        return K.skip_lora_grouped_bwd.lower(
            s((L, M, D), BF16), s((N, L, D, R), F32), s((N, L, R, D), F32),
            s((M, D), BF16), tiles,
        )
    if name == "skip_lora_grouped_fwd_int8":
        return K.skip_lora_grouped_fwd_int8.lower(
            s((L, M, D), BF16), s((N, L, D, R), I8), s((N, L, D), F32),
            s((N, L, R, D), I8), s((N, L, R), F32), tiles,
        )
    if name == "skip_lora_grouped_fwd_q4":
        return K.skip_lora_grouped_fwd_q4.lower(
            s((L, M, D), BF16), s((N, L, D, R // 2), U8), s((N, L, D), F32),
            s((N, L, R, D // 2), U8), s((N, L, R), F32), s((1, 16), F32),
            tiles,
        )
    if name == "skip_lora_grouped_fwd_actint8":
        return K.skip_lora_grouped_fwd_actint8.lower(
            s((L, M, D), I8), s((L, M), F32), s((N, L, D, R), F32),
            s((N, L, R, D), F32), tiles,
        )
    if name == "skip_lora_fwd_int8":
        return K.skip_lora_fwd_int8.lower(
            s((L, M, D), I8), s((L, M), F32), s((L, D, R), F32),
            s((L, R, D), F32),
        )
    if name == "flash_attention_fwd":
        qkv = s((HEADS, S, HD), BF16)
        return FK.flash_attention_fwd.lower(qkv, qkv, qkv)
    if name == "paged_gather":
        return FP.paged_gather.lower(
            s((N_BLOCKS, BLOCK, HEADS, HD), BF16), s((4, S // BLOCK), I32)
        )
    raise KeyError(name)


KERNELS = [
    "skip_lora_fwd",
    "skip_lora_bwd",
    "skip_lora_grouped_fwd_ml",
    "skip_lora_grouped_fwd_lm",
    "skip_lora_grouped_bwd",
    "skip_lora_grouped_fwd_int8",
    "skip_lora_grouped_fwd_q4",
    "skip_lora_grouped_fwd_actint8",
    "skip_lora_fwd_int8",
    "flash_attention_fwd",
    "paged_gather",
]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip):
    compiled = _lowered(name, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the session's programs keep their kernels' op names ---------------------
#
# The benchmark's roofline readers find the grouped kernels in a device
# trace by op name (``skip_lora_grouped_fwd`` in serving,
# ``transpose_jvp_jit_skip_lora_grouped_bwd`` in adapt). Those names come
# from the JAX name stack, so a ``named_scope`` in the wrong place renames
# them. Each program below compiles for the described chip at musicgen-
# medium width, 2 layers, with the kernels lowered by Mosaic.

PROGRAM_KERNELS = {
    "sched_step": {"skip_lora_grouped_fwd"},
    "sched_admit": {"skip_lora_grouped_fwd"},
    "ingest": {"skip_lora_grouped_fwd"},
    "fleet_cached_epoch": {"jvp_jit_skip_lora_grouped_fwd__",
                           "transpose_jvp_jit_skip_lora_grouped_bwd___"},
}


@pytest.fixture
def mosaic(monkeypatch):
    """Lower Pallas kernels for the chip, not in the CPU's interpret mode."""
    from repro.kernels.flash_attn import ops as FO
    from repro.kernels.flash_attn import paged as FPG
    from repro.kernels.skip_lora import ops as SO

    for mod in (SO, FO, FPG):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _program(name: str, sharding):
    import dataclasses

    from repro.configs import get_config
    from repro.core import fleet_finetune as FF
    from repro.core import lm_skiplora as SL
    from repro.core import runtime as RT
    from repro.core import scheduler as S
    from repro.core.skip_cache import SkipCache
    from repro.models.lm import init_lm, init_serve_caches
    from repro.optim.optimizers import adamw

    cfg = dataclasses.replace(get_config("musicgen-medium"), n_layers=2,
                              family="dense", frontend=None, frontend_seq=0)
    lay, d, r, n = cfg.n_layers, cfg.d_model, 8, 4

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def abstract(tree):
        return jax.tree.map(lambda x: s(x.shape, x.dtype), tree)

    params = abstract(jax.eval_shape(lambda: init_lm(jax.random.key(0), cfg)))
    pools = {"A": s((n, lay, d, r), F32), "B": s((n, lay, r, d), F32)}
    if name in ("sched_step", "sched_admit"):
        b, p, max_seq, a = 4, 64, 128, 2
        live = (abstract(jax.eval_shape(lambda: init_serve_caches(cfg, b, max_seq))),
                s((b, 1), I32), s((b,), I32), s((b,), jnp.bool_), s((b,), F32),
                abstract(jax.eval_shape(lambda: jax.random.key(0))))
        if name == "sched_step":
            return S._sched_step_fn(cfg, True, 4, max_seq).lower(
                params, pools, s((b,), I32), *live)
        return S._sched_admit_fn(cfg, True, 4, max_seq, a, p).lower(
            params, pools, s((b,), I32), s((a, p), I32), s((a,), I32),
            s((a,), I32), s((a,), I32), *live)
    if name == "ingest":
        return RT._ingest_fn(cfg, True).lower(params, s((2, 128), I32), pools,
                                              s((2,), I32))
    sl = SL.SkipLoRAConfig(rank=r, mode="full", cache_dtype="float32",
                           use_fused_kernel=True)
    spt, seq, bpt = 4, 128, 2
    layout = SL.lm_cache_layout(cfg, sl, seq)
    cache = SkipCache(
        slots={k: s((n * spt,) + shp, dt) for k, (shp, dt) in layout.items()},
        valid=s((n * spt,), jnp.bool_))
    opt = adamw(1e-3)
    stacked = {"A": s((n, lay, d, r), F32), "B": s((n, lay, r, d), F32)}
    ostate = abstract(jax.eval_shape(lambda: opt.init(jax.tree.map(
        lambda x: jnp.zeros(x.shape, x.dtype), stacked))))
    epoch = FF.make_fleet_cached_epoch(cfg, sl, opt, n, use_kernel=True,
                                       donate=False)
    return epoch.lower(params, stacked, ostate, cache,
                       s((spt // bpt, n * bpt), I32), s((n * bpt,), I32))


@pytest.mark.parametrize("name", sorted(PROGRAM_KERNELS))
def test_program_keeps_kernel_op_names(name, one_chip, mosaic):
    text = _program(name, one_chip).compile().as_text()
    assert re.search(rf"^HloModule jit_{name},", text, re.M)
    kernels = {
        re.sub(r"\.\d+$", "", m)
        for m in re.findall(
            r"^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text, re.M)
    }
    assert kernels == PROGRAM_KERNELS[name]


# -- a decode step writes one token into the carried KV cache ----------------
#
# The decode layer scan carries each attention position's stacked K/V and
# writes the step's token into it in place. Inside the decode loops (the
# chunk scan and the layer scan within it) no op may copy, slice out or
# write back the stacked cache or one layer of it; the one cache-shaped op
# left is the in-place write whose update is one token per row.

_INSTR = re.compile(
    r"^\s*(ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)")
_MOVES = {"copy", "copy-done", "dynamic-slice", "dynamic-update-slice",
          "scatter", "fusion"}
_UPDATE = {"scatter": 2, "dynamic-update-slice": 1}


def _decode_loop_cache_moves(text, cache_shapes, token_elems):
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head[1], [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    shape, root = {}, {}
    for name, lines in comps.items():
        for line in lines:
            m = _INSTR.match(line)
            if m:
                shape[m[2]] = tuple(int(d) for d in m[3].split(",") if d)
                if m[1]:
                    root[name] = m

    def token_write(m):
        i = _UPDATE.get(m[4])
        if i is None:
            return False
        upd = re.findall(r"%([\w.\-]+)", m[5])[i]
        return int(np.prod(shape[upd])) <= token_elems

    todo = re.findall(r"while\([^\n]*?body=%([\w.\-]+)[^\n]*?"
                      r"op_name=\"jit\(\w+\)/decode/while", text)
    assert todo, "no decode loop found"
    seen, moves = set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(r"(?:body|to_apply)=%([\w.\-]+)", line)
            m = _INSTR.match(line)
            if not m or m[4] not in _MOVES or shape[m[2]] not in cache_shapes:
                continue
            if m[4] == "fusion":
                called = re.search(r"calls=%([\w.\-]+)", line)[1]
                if token_write(root[called]):
                    continue
            elif token_write(m):
                continue
            moves.append(line.strip()[:160])
    return moves


@pytest.mark.parametrize("name", ["sched_step", "sched_admit"])
def test_decode_loop_moves_no_whole_cache(name, one_chip, mosaic):
    from repro.configs import get_config

    cfg = get_config("musicgen-medium")
    nk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    layer = (4, 128, nk, hd)                 # _program's rows and max_seq
    text = _program(name, one_chip).compile().as_text()
    moves = _decode_loop_cache_moves(text, {(2,) + layer, layer}, 4 * nk * hd)
    assert moves == []
