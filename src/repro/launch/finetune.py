"""Skip2-LoRA fine-tuning launcher — a thin CLI over the session runtime.

The paper's Algorithm 1 as a one-tenant continual session (DESIGN.md §9):
epoch 0 *ingests* the fine-tune set (populate forwards that write the
activation cache — and would serve logits back in a live deployment);
every later epoch is a cached ``adapt`` with ZERO backbone compute.
Compare wall-clock per epoch to see the paper's claim live:

  PYTHONPATH=src python -m repro.launch.finetune --arch stablelm-1.6b \
      --reduced --epochs 4 --samples 64 --batch 8 --seq 128 --mode full

With ``--hbm-mb`` the runtime's ``TieredCacheEngine`` places the cache
under that budget: rows beyond it spill to the host tier and ``adapt``
takes the streaming prefetch path instead of the fused scan (the §9 path
table). Tier hit counts are reported at the end.

``--mode freeze_a`` (R-wide compressed cache; not a fleet-trainable mode)
keeps the single-tenant scan loop from ``core.lm_skiplora`` directly.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core import lm_skiplora as SL
from repro.data.pipeline import DataConfig, make_pipeline
from repro.models.lm import init_lm
from repro.optim.optimizers import adamw


def _parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mode", default="full", choices=["full", "int8", "freeze_a"])
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--hbm-mb", type=float, default=0.0,
                    help="cache HBM budget in MiB; 0 = fully device-resident")
    ap.add_argument("--cache-dir", default=None,
                    help="host-tier directory (disk spill); default in-memory")
    return ap.parse_args(argv)


def main(argv=None) -> dict:
    args = _parse(argv)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    sl = SL.SkipLoRAConfig(
        rank=args.rank, mode=args.mode, cache_dtype="float32",
        use_fused_kernel=args.use_kernel,
    )
    print(
        f"arch={cfg.name} mode={sl.mode} rank={sl.rank} "
        f"cache/sample={SL.cache_nbytes_per_sample(cfg, sl, args.seq)/2**20:.2f} MiB"
    )

    params = init_lm(jax.random.key(0), cfg)
    dcfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, num_samples=args.samples,
    )
    store, _ = make_pipeline(dcfg)
    staged = store.batch(np.arange(args.samples))
    tokens = jnp.asarray(staged["tokens"])
    labels = jnp.asarray(staged["labels"])

    if args.mode == "freeze_a":
        return _legacy_freeze_a(args, cfg, sl, params, tokens, labels)

    from repro.core.runtime import SessionRuntime

    rt = SessionRuntime(
        cfg, sl, params, max_tenants=1, samples_per_tenant=args.samples,
        seq=args.seq, lr=args.lr, use_kernel=args.use_kernel,
        hbm_budget_bytes=(int(args.hbm_mb * 2**20) if args.hbm_mb > 0 else None),
        cache_dir=args.cache_dir,
    )
    if args.hbm_mb > 0:
        print(f"tiered engine: HBM budget {args.hbm_mb:g} MiB -> "
              f"{rt.engine.capacity}/{args.samples} rows resident")

    epoch_times, losses = [], []
    key = jax.random.key(1)
    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        if epoch == 0:
            # Populate phase: ingest the whole set (backbone forward once
            # per sample; in a live session these logits serve the caller).
            for lo in range(0, args.samples, args.batch):
                rt.ingest("device-0", tokens[lo:lo + args.batch],
                          labels[lo:lo + args.batch])
        out = rt.adapt(epochs=1, batch_per_tenant=args.batch, key=key)
        ls = out["losses"]["device-0"]
        jax.block_until_ready(ls)
        dt = time.perf_counter() - t0
        epoch_times.append(dt)
        losses.append(float(ls.mean()))  # mean epoch loss (order-robust)
        kind = "populate" if epoch == 0 else "cached  "
        print(f"epoch {epoch} [{kind}] loss {losses[-1]:.4f} time {dt:.2f}s "
              f"({out['path']} path)")

    if len(epoch_times) > 1:
        speedup = epoch_times[0] / (sum(epoch_times[1:]) / len(epoch_times[1:]))
        print(f"cached-epoch speedup vs populate epoch: {speedup:.1f}x")
    out = {"epoch_times": epoch_times, "losses": losses}
    if args.hbm_mb > 0:
        st = rt.engine.stats
        print(f"cache tiers: hbm_hits={st.hbm_hits} host_hits={st.host_hits} "
              f"staged_hits={st.staged_hits} spills={st.spills} "
              f"hbm_hit_rate={st.hbm_hit_rate():.2f}")
        out["cache_stats"] = st
    return out


def _legacy_freeze_a(args, cfg, sl, params, tokens, labels) -> dict:
    """freeze_a trains only B against an R-wide cache — outside the fleet
    trainer's modes, so it keeps the PR 1 single-tenant scan loop."""
    from repro.core.finetune import epoch_index_matrix

    adapters = SL.init_adapters(jax.random.key(1), cfg, sl)
    trainable, static = SL.split_trainable(adapters, sl)
    opt = adamw(args.lr)
    opt_state = opt.init(trainable)
    cache = SL.init_lm_cache(args.samples, cfg, sl, args.seq)
    populate_epoch = SL.make_populate_epoch(cfg, sl, opt)
    cached_epoch = SL.make_cached_epoch(cfg, sl, opt)
    epoch_times, losses = [], []
    rng = jax.random.key(2)
    for epoch in range(args.epochs):
        rng, sk = jax.random.split(rng)
        idx_mat = epoch_index_matrix(sk, args.samples, args.batch)
        t0 = time.perf_counter()
        if epoch == 0:
            trainable, opt_state, cache, ls = populate_epoch(
                params, trainable, static, opt_state, cache,
                tokens, labels, idx_mat,
            )
        else:
            trainable, opt_state, ls = cached_epoch(
                params, trainable, static, opt_state, cache, idx_mat
            )
        jax.block_until_ready(ls)
        dt = time.perf_counter() - t0
        epoch_times.append(dt)
        losses.append(float(ls[-1]))
        kind = "populate" if epoch == 0 else "cached  "
        print(f"epoch {epoch} [{kind}] loss {losses[-1]:.4f} time {dt:.2f}s")
    if len(epoch_times) > 1:
        speedup = epoch_times[0] / (sum(epoch_times[1:]) / len(epoch_times[1:]))
        print(f"cached-epoch speedup vs populate epoch: {speedup:.1f}x")
    return {"epoch_times": epoch_times, "losses": losses}


if __name__ == "__main__":
    from repro.launch import enable_compile_cache

    enable_compile_cache()
    main()
