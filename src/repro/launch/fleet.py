"""Sharded fleet fine-tuning launcher — a thin CLI over the mesh-native
``SessionRuntime``.

Single- and multi-device fleets now run through ONE engine: the session
runtime ingests every tenant's samples (the populate forwards), then runs
per-epoch grouped ``adapt`` calls with pool write-back. On a multi-device
mesh the runtime places each tenant's adapters, optimizer moments, and
cache partition on its logical shard's device and dispatches every
(trajectory, shard) group's fused epochs shard-locally (DESIGN.md §10) —
the bespoke ``shard_map`` data-parallel path this launcher used to carry
collapsed into the runtime, which is now the one way to run multi-device
fine-tuning.

CPU verification (no hardware needed): the device count is forced *before*
jax import, exactly like ``launch/dryrun.py``:

  PYTHONPATH=src python -m repro.launch.fleet --arch stablelm-1.6b \
      --reduced --tenants 4 --devices 2 --samples 8 --batch-per-tenant 4 \
      --seq 16 --epochs 3 --check-parity

``--check-parity`` compares against the offline single-dispatch
``fleet_finetune`` trainer: at ``--devices 1`` the session reproduces it
BITWISE on the kernel path (the §9 bar, zero tolerance); at ``--devices N``
the per-shard groups train fewer tenants per dispatch than the offline
joint fleet, and under a forced host-device count XLA compiles
shape-dependent reductions, so parity is held to 1e-5 (the same tolerance
the legacy shard_map path needed, for the same reason — see DESIGN.md §10;
the *zero*-tolerance multi-device bar is ``launch/run.py --check-parity``,
which pins the group layout and varies only device placement).
"""

from __future__ import annotations

import argparse
import os
import time


def _parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--devices", type=int, default=1,
                    help="tenant-parallel devices (forced on CPU via XLA_FLAGS)")
    ap.add_argument("--samples", type=int, default=8, help="samples per tenant")
    ap.add_argument("--batch-per-tenant", type=int, default=4)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--mode", default="full", choices=["full", "int8"])
    ap.add_argument("--use-kernel", action="store_true",
                    help="grouped Pallas kernel (interpret mode off-TPU)")
    ap.add_argument("--check-parity", action="store_true",
                    help="compare session losses/adapters against the "
                         "offline fleet trainer")
    return ap.parse_args(argv)


def main(argv=None) -> dict:
    args = _parse_args(argv)
    if (
        args.devices > 1
        and os.environ.get("JAX_PLATFORMS") == "cpu"
        and "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
    ):
        # Host devices are a CPU rehearsal only: a run that finds no chip
        # must never pass on them. Must land before JAX starts its backend.
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax

    from repro.configs import get_config, reduce_config
    from repro.core import lm_skiplora as SL

    if args.tenants % args.devices:
        raise SystemExit(
            f"--tenants {args.tenants} must divide over --devices {args.devices}"
        )
    if len(jax.devices()) < args.devices:
        raise SystemExit(
            f"need {args.devices} devices, have {len(jax.devices())} (on the "
            "CPU, run with JAX_PLATFORMS=cpu to force host devices)"
        )

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    sl = SL.SkipLoRAConfig(rank=args.rank, mode=args.mode, cache_dtype="float32",
                           use_fused_kernel=args.use_kernel)

    n_t, n_per = args.tenants, args.samples
    bpt = min(args.batch_per_tenant, n_per)  # fleet_index_matrix clamp

    from repro.models.lm import init_lm

    params = init_lm(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (n_t, n_per, args.seq), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.key(2), (n_t, n_per, args.seq), 0, cfg.vocab_size)

    return _runtime_main(args, cfg, sl, params, tokens, labels, bpt)


def _runtime_main(args, cfg, sl, params, tokens, labels, bpt) -> dict:
    """Fleet epochs as one interleaved runtime session over the mesh:
    ingest every tenant's samples (the populate forwards, one per tenant —
    identical shapes on any device count), then per-epoch grouped ``adapt``
    calls with pool write-back, each (trajectory, shard) group dispatched
    on its own device. At ``--devices 1`` this is bitwise-identical to
    ``fleet_finetune`` on the kernel path (DESIGN.md §9), which
    ``--check-parity`` asserts at zero tolerance."""
    import time

    import jax
    import numpy as np

    from repro.core import fleet_finetune as FF
    from repro.core.runtime import SessionRuntime
    from repro.optim.optimizers import adamw
    from repro.runtime.sharding import make_mesh

    if args.check_parity and args.mode != "full":
        raise SystemExit(
            "--check-parity on the runtime path requires --mode full: int8 "
            "cached epochs intentionally train on the quantised cache, "
            "while the offline populate epoch steps on full-precision "
            "activations (DESIGN.md §9)"
        )
    n_t, n_per = args.tenants, args.samples
    mesh = make_mesh(
        (args.devices,), ("data",), devices=jax.devices()[: args.devices]
    )
    rt = SessionRuntime(
        cfg, sl, params, max_tenants=n_t, samples_per_tenant=n_per,
        seq=args.seq, lr=args.lr, use_kernel=args.use_kernel, mesh=mesh,
    )
    t0 = time.perf_counter()
    for t in range(n_t):
        for lo in range(0, n_per, bpt):
            rt.ingest(t, tokens[t, lo:lo + bpt], labels[t, lo:lo + bpt])
    ingest_s = time.perf_counter() - t0

    losses, times = [], []
    for e in range(args.epochs):
        t0 = time.perf_counter()
        out = rt.adapt(epochs=1, batch_per_tenant=bpt, key=jax.random.key(3))
        ls = np.stack([out["losses"][t][0] for t in range(n_t)], axis=-1)
        dt = time.perf_counter() - t0
        times.append(dt)
        losses.append(ls)
        kind = "populate" if e == 0 else "cached  "
        extra = f" (+{ingest_s:.2f}s ingest)" if e == 0 else ""
        print(f"epoch {e} [{kind}] mean loss {float(np.mean(ls)):.4f} "
              f"time {dt:.2f}s{extra} ({n_t / dt:.1f} tenants/s/epoch, "
              f"{len(out['groups'])} shard group(s))")

    losses = np.stack(losses)  # (epochs, steps, n_tenants)
    out = {"losses": losses, "epoch_times": times, "devices": args.devices}

    if args.check_parity:
        ref = FF.fleet_finetune(
            jax.random.key(3), cfg, sl, params, tokens, labels,
            epochs=args.epochs, batch_per_tenant=bpt, optimizer=adamw(args.lr),
            use_kernel=args.use_kernel,
        )
        diff = float(np.max(np.abs(ref.losses - losses)))
        adiff = max(
            float(np.max(np.abs(
                np.asarray(rt.tenant(t).adapters[k]) - np.asarray(ref.adapters[k][t])
            )))
            for t in range(n_t) for k in ("A", "B")
        )
        print(f"parity_max_abs_diff={diff:.3e}")
        print(f"parity_adapter_diff={adiff:.3e}")
        out["parity_max_abs_diff"] = diff
        out["parity_adapter_diff"] = adiff
        # The single-device session reproduces the offline trainer BITWISE
        # (the §9 bar); sharded groups differ from the offline joint fleet
        # only by shape-dependent XLA reduction compilation — 1e-5 bounds
        # it with orders of magnitude to spare (measured ~1e-6).
        tol = 0.0 if args.devices == 1 else 1e-5
        if diff > tol or adiff > tol:
            # The CI verification step must FAIL on divergence, not just
            # print it.
            raise SystemExit(
                f"session/offline parity broken: losses {diff:.3e} "
                f"adapters {adiff:.3e} (tol {tol:.0e})"
            )
    return out


if __name__ == "__main__":
    from repro.launch import enable_compile_cache

    enable_compile_cache()
    main()
