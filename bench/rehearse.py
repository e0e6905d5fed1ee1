"""Compile a cell's programs at its real size for a described TPU v5e, with
no chip, and print each program's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload stablelm-chat \
        [--max-batch 32]

Serve cells compile the scheduler's admission and decode-step programs;
fine-tune cells the ingest forward and one fused adapt epoch. The bytes a
program needs beside its arguments (``temp``) plus the arguments the
process keeps (weights, KV caches, pool, skip cache) bound what fits in
the chip's 16 GB.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _abstract(tree, sharding):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _report(name, compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes} output "
          f"{m.output_size_in_bytes} alias {m.alias_size_in_bytes} temp "
          f"{m.temp_size_in_bytes} -> {total} bytes ({total / 2**30:.2f} GiB)")
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--max-batch", type=int, default=0,
                    help="override the mix's scheduler max_batch")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model as M
    from bench.run import load_cell
    from repro.core import fleet_finetune as FF
    from repro.core import lm_skiplora as SL
    from repro.core import runtime as RT
    from repro.core import scheduler as S
    from repro.models.lm import init_serve_caches
    from repro.optim.optimizers import adamw

    jax.config.update("jax_enable_compilation_cache", False)
    cell = load_cell(ROOT, args.workload)
    cfg, mix = cell["config"], cell["mix"]
    model = M.model(cfg, ROOT)
    mcfg = model.program_config(cfg)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    params = _abstract(jax.eval_shape(lambda: model.make_params(cfg, 0)), one)
    rank = int(cfg["skip_lora_rank"])
    L, D = mcfg.n_layers, mcfg.d_model
    f32 = jnp.float32
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    print(f"{args.workload}: weights "
          f"{sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))} bytes")
    if mix.get("serve"):
        sc = dict(mix["scheduler"])
        if args.max_batch:
            sc["max_batch"] = args.max_batch
        b, p = sc["max_batch"], sc["max_prompt"]
        max_seq = p + sc["max_new_cap"]
        a = sc["admit_bucket"]
        n_slots = int(mix["serve"]["tenants"]) + 2
        pools = {"A": sds((n_slots, L, D, rank), f32),
                 "B": sds((n_slots, L, rank, D), f32)}
        caches = _abstract(jax.eval_shape(
            lambda: init_serve_caches(mcfg, b, max_seq)), one)
        kv = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
        print(f"max_batch {b}: KV caches {kv} bytes at max_seq {max_seq}")
        i32 = jnp.int32
        live = (caches, sds((b, 1), i32), sds((b,), i32), sds((b,), jnp.bool_),
                sds((b,), f32), _abstract(jax.eval_shape(lambda: jax.random.key(0)), one))
        step = S._sched_step_fn(mcfg, True, sc["chunk"], max_seq)
        _report("sched_step", step.lower(params, pools, sds((b,), i32), *live).compile())
        admit = S._sched_admit_fn(mcfg, True, sc["chunk"], max_seq, a, p)
        _report("sched_admit", admit.lower(
            params, pools, sds((b,), i32), sds((a, p), i32), sds((a,), i32),
            sds((a,), i32), sds((a,), i32), *live).compile())
    if mix.get("finetune"):
        ft = mix["finetune"]
        n, spt, seq = int(ft["cohort"]), int(ft["samples"]), int(ft["seq"])
        sl = SL.SkipLoRAConfig(rank=rank, mode="full",
                               cache_dtype=cfg["skip_cache_dtype"],
                               use_fused_kernel=True)
        pools = {"A": sds((n + 1, L, D, rank), f32),
                 "B": sds((n + 1, L, rank, D), f32)}
        ib = int(ft["ingest_batch"])
        ingest = RT._ingest_fn(mcfg, True)
        _report("ingest", ingest.lower(
            params, sds((ib, seq), jnp.int32), pools,
            sds((ib,), jnp.int32)).compile())
        layout = SL.lm_cache_layout(mcfg, sl, seq)
        from repro.core.skip_cache import SkipCache
        cache = SkipCache(
            slots={k: sds((n * spt,) + shp, dt) for k, (shp, dt) in layout.items()},
            valid=sds((n * spt,), jnp.bool_),
        )
        nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        print(f"skip cache {nbytes} bytes for {n} x {spt} samples of {seq}")
        opt = adamw(float(cfg["adapt_lr"]))
        stacked = {"A": sds((n, L, D, rank), f32), "B": sds((n, L, rank, D), f32)}
        ostate = _abstract(jax.eval_shape(lambda: opt.init(
            {"A": jnp.zeros((n, L, D, rank)), "B": jnp.zeros((n, L, rank, D))})), one)
        bpt = int(ft["batch_per_tenant"])
        steps = -(-spt // bpt)
        epoch = FF.make_fleet_cached_epoch(mcfg, sl, opt, n, use_kernel=True,
                                           donate=False)
        _report("adapt epoch", epoch.lower(
            params, stacked, ostate, cache, sds((steps, n * bpt), jnp.int32),
            sds((n * bpt,), jnp.int32)).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
