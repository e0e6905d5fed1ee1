"""End-to-end training launcher (runs on whatever devices exist).

Full-backprop baseline training of any ``--arch`` (reduced or full config)
with AdamW, gradient clipping, deterministic resumable data, checkpointing
and the fault supervisor. On the CPU container this drives reduced configs
(examples/ use it to train a ~100M model); on a pod the same entry point
runs the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --reduced --steps 200 --batch 8 --seq 128

``--scan-chunk N`` fuses N steps into one ``jax.lax.scan`` dispatch over
pre-sampled batch ids (the whole synthetic fine-tune set is staged on
device). This is the same dispatch-amortisation strategy the Skip2-LoRA
epoch loops use (DESIGN.md §2); the supervisor/straggler path stays on the
default per-step loop.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduce_config
from repro.data.pipeline import DataConfig, make_pipeline
from repro.launch.mesh import make_debug_mesh
from repro.models.lm import init_lm, train_loss_fn
from repro.optim.optimizers import adamw, apply_updates, clip_by_global_norm
from repro.runtime.fault import Supervisor


def make_step(cfg, opt):
    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lambda p: train_loss_fn(p, cfg, batch))(params)
        grads = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss

    return step


def make_scan_chunk(cfg, opt):
    """A chunk of train steps as one compiled dispatch: scan over an
    (n_steps, batch) id matrix gathering from device-staged tokens/labels."""

    def run_chunk(params, opt_state, tokens, labels, idx_mat):
        def body(carry, idx):
            p, o = carry
            batch = {"tokens": tokens[idx], "labels": labels[idx]}
            loss, grads = jax.value_and_grad(
                lambda q: train_loss_fn(q, cfg, batch)
            )(p)
            grads = clip_by_global_norm(grads, 1.0)
            updates, o = opt.update(grads, o, p)
            p = apply_updates(p, updates)
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), idx_mat
        )
        return params, opt_state, losses

    return jax.jit(run_chunk, donate_argnums=(0, 1))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--scan-chunk", type=int, default=0,
                    help="fuse N steps per dispatch via lax.scan (0 = off)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    print(f"arch={cfg.name} layers={cfg.n_layers} d={cfg.d_model} params={cfg.param_count():,}")

    key = jax.random.key(0)
    params = init_lm(key, cfg)
    opt = adamw(args.lr, weight_decay=0.1)
    opt_state = opt.init(params)
    step_fn = make_step(cfg, opt)

    dcfg = DataConfig(
        vocab_size=cfg.vocab_size,
        seq_len=args.seq,
        global_batch=args.batch,
        num_samples=max(args.batch * 8, 256),
    )
    store, sampler = make_pipeline(dcfg)

    ckpt = CheckpointManager(args.ckpt_dir, save_every=args.ckpt_every)
    sup = Supervisor(ckpt)

    state = {"params": params, "opt": opt_state}
    t_start = time.time()
    losses = []

    if args.scan_chunk > 0:
        # Fused path: chunks of steps in one dispatch; checkpoint per chunk.
        run_chunk = make_scan_chunk(cfg, opt)
        staged = store.batch(np.arange(dcfg.num_samples))
        tokens = jnp.asarray(staged["tokens"])
        labels = jnp.asarray(staged["labels"])
        params, opt_state = state["params"], state["opt"]
        step = 0
        while step < args.steps:
            n = min(args.scan_chunk, args.steps - step)
            idx_mat = jnp.asarray(
                np.stack([sampler.next_ids() for _ in range(n)])
            )
            params, opt_state, ls = run_chunk(
                params, opt_state, tokens, labels, idx_mat
            )
            jax.block_until_ready(ls)
            losses.extend(np.asarray(ls, np.float32).tolist())
            prev = step
            step += n
            dt = time.time() - t_start
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"({dt:.1f}s, {n} steps/dispatch)")
            # Save whenever the chunk crossed a save boundary (chunk size
            # need not divide --ckpt-every).
            if prev // args.ckpt_every != step // args.ckpt_every:
                ckpt.save(step, {"params": params, "opt": opt_state})
        print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
        return

    def run_one(state, step):
        ids = sampler.next_ids()
        batch_np = store.batch(ids)
        batch = {
            "tokens": jnp.asarray(batch_np["tokens"]),
            "labels": jnp.asarray(batch_np["labels"]),
        }
        params, opt_state, loss = step_fn(state["params"], state["opt"], batch)
        losses.append(float(loss))
        if step % args.log_every == 0:
            dt = time.time() - t_start
            print(f"step {step:5d} loss {float(loss):.4f} ({dt:.1f}s)")
        return {"params": params, "opt": opt_state}

    state = sup.run(state, run_one, num_steps=args.steps)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")


if __name__ == "__main__":
    from repro.launch import enable_compile_cache

    enable_compile_cache()
    main()
