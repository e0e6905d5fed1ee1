"""Multi-tenant serving launcher — a thin CLI over ``core.runtime``.

The serving engine itself (compiled-function cache, scan-fused decode,
grouped adapter routing) lives in ``repro.core.runtime`` since the session
runtime unified serve and fleet fine-tune over one adapter pool (DESIGN.md
§9); this module re-exports the generation entry points for existing
callers (benchmarks, examples, tests) and keeps the CLI:

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
      --reduced --batch 4 --prompt-len 32 --gen 16 --tenants 3

The multi-tenant path routes through a ``SessionRuntime`` (pool lookup +
path selection per batch); the single-stack path calls ``generate``
directly. Both hit the same shared compiled-fn cache, so the runtime adds
no retrace or rebuild over the PR 2 engine.
"""

from __future__ import annotations

import argparse
import functools
import time

import jax

from repro.configs import get_config, reduce_config
from repro.core import lm_skiplora as SL
from repro.core.adapter_pool import AdapterPool  # noqa: F401 (re-export)
from repro.core.runtime import (  # noqa: F401 (public re-exports)
    _FN_CACHE,
    _cached_fn,
    _decode_scan_fn,
    _decode_step_fn,
    _prefill_fn,
    _prefill_grouped_fn,
    SessionRuntime,
    generate,
    generate_grouped,
    generate_loop,
)
from repro.models.lm import init_lm


def _demo_runtime(cfg, n_tenants: int, rank: int, compress, params) -> SessionRuntime:
    """Session with ``n_tenants`` pretend on-device fine-tunes (B != 0)."""
    sl = SL.SkipLoRAConfig(rank=rank)
    rt = SessionRuntime(
        cfg, sl, params, max_tenants=n_tenants, samples_per_tenant=1, seq=8,
        pool_compress=compress,
    )
    for t in range(n_tenants):
        ad = SL.init_adapters(jax.random.key(100 + t), cfg, sl)
        ad["B"] = jax.random.normal(jax.random.key(200 + t), ad["B"].shape) * 0.02
        rt.pool.register(f"tenant-{t}", ad)
    return rt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--with-adapters", action="store_true")
    ap.add_argument("--tenants", type=int, default=0,
                    help="serve a multi-tenant batch over this many adapters")
    ap.add_argument("--pool-compress", choices=["int8"], default=None)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--unroll", type=int, default=1,
                    help="decode steps fused per scan iteration")
    ap.add_argument("--loop", action="store_true",
                    help="use the per-token loop instead of the fused scan")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve through the continuous-batching request "
                         "scheduler (one request per batch row, staggered "
                         "admission) instead of one pre-formed batch")
    ap.add_argument("--chunk", type=int, default=4,
                    help="decode steps per scheduler dispatch")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    params = init_lm(jax.random.key(0), cfg)
    prompts = jax.random.randint(
        jax.random.key(3), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )

    if args.scheduler:
        if args.loop:
            ap.error("--loop and --scheduler are mutually exclusive")
        rt = _demo_runtime(cfg, max(args.tenants, 1), args.rank,
                           args.pool_compress, params)
        rt.attach_scheduler(
            max_batch=args.batch, max_prompt=args.prompt_len,
            max_new_cap=args.gen, chunk=args.chunk,
            admit_bucket=min(2, args.batch),
        )
        tenants = [None] + [
            f"tenant-{i % max(args.tenants, 1)}" for i in range(1, args.batch)
        ]
        t0 = time.perf_counter()
        reqs = [
            rt.enqueue_serve(t, prompts[i], max_new=args.gen,
                             temperature=args.temperature)
            for i, t in enumerate(tenants)
        ]
        rt.drain()
        dt = time.perf_counter() - t0
        toks = jax.numpy.stack([jax.numpy.asarray(r.result()) for r in reqs])
        c = rt.scheduler.counters
        print(f"[scheduler: {c['dispatch/admit']} admit + "
              f"{c['dispatch/step']} step dispatches, chunk {args.chunk}]")
        print(f"generated {toks.shape} in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s incl. compile)")
        print("first sequences:", toks[:2, :8].tolist())
        return

    if args.tenants > 0:
        if args.loop:
            ap.error("--loop applies to single-stack serving; the grouped "
                     "multi-tenant path always uses the fused scan")
        rt = _demo_runtime(cfg, args.tenants, args.rank, args.pool_compress,
                           params)
        # Mixed batch: rows cycle through tenants; row 0 serves the base
        # model via the pinned zero slot.
        tenants = [None] + [
            f"tenant-{i % args.tenants}" for i in range(1, args.batch)
        ]
        t0 = time.perf_counter()
        toks = rt.serve(
            tenants, prompts, max_new=args.gen,
            temperature=args.temperature, unroll=args.unroll,
        )
        jax.block_until_ready(toks)
        dt = time.perf_counter() - t0
        print(f"[grouped x{args.tenants} tenants, pool "
              f"{rt.pool.nbytes() / 2**20:.1f} MiB, "
              f"compress={args.pool_compress}]")
    else:
        adapters_stack = None
        if args.with_adapters:
            sl = SL.SkipLoRAConfig(rank=args.rank)
            ad = SL.init_adapters(jax.random.key(1), cfg, sl)
            ad["B"] = jax.random.normal(jax.random.key(2), ad["B"].shape) * 0.01
            adapters_stack = SL.adapters_to_stack(ad, cfg)
        gen_fn = generate_loop if args.loop else functools.partial(
            generate, unroll=args.unroll
        )
        t0 = time.perf_counter()
        toks = gen_fn(
            params, cfg, prompts, max_new=args.gen,
            adapters_stack=adapters_stack, temperature=args.temperature,
        )
        jax.block_until_ready(toks)
        dt = time.perf_counter() - t0

    n_disp = args.gen if args.loop else 1
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s incl. compile; "
          f"{n_disp} decode dispatch{'es' if n_disp > 1 else ''})")
    print("first sequences:", toks[:2, :8].tolist())


if __name__ == "__main__":
    from repro.launch import enable_compile_cache

    enable_compile_cache()
    main()
