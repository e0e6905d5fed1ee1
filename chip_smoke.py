"""Chip bring-up check: the serve -> ingest -> adapt session on a TPU.

    python chip_smoke.py            # one chip: stablelm-1.6b session, checks (a)-(e)
    python chip_smoke.py --chips 4  # four chips: the 2x2 (data x model) mesh
                                    # session in float32 against its
                                    # 1-device twin only

The one-chip run drives ``repro.launch.run``'s session at the published
width of stablelm-1.6b (24 layers, d 2048, 32 heads, d_ff 5632, vocab
100352, bf16; random weights from a fixed seed): a base serve, per-tenant
ingest, a fused adapt, and a mixed serve, both serves through the request
scheduler, with the grouped skip-LoRA Pallas kernels on. It runs the event
stream twice on fresh runtimes: the first pass compiles, the second is
warm. A phase's compile seconds are its first-pass time less its
second-pass time; its run seconds are the second pass, each ending once
its outputs are ready. Then it checks:

  (a) every request completed with ``gen`` in-vocab tokens;
  (b) adapt losses are finite and each tenant's last-epoch mean loss is
      below its first-epoch mean;
  (c) at this width, on the session's own adapter pool, each skip-LoRA
      Pallas kernel the session's options can select matches its jnp
      oracle (``kernels/skip_lora/ref.py``): the grouped serve kernel over
      a float, int8, int4 and nf4 pool, the grouped adapt kernel over an
      int8 activation cache, and the single-adapter int8-cache kernel;
      max|kernel - oracle| <= KERNEL_TOL * max|oracle| for each;
  (d) the session's last-position logits for a tenant's ingest rows match
      a float32 forward (matmuls at highest precision) of the same params
      and the tenant's served adapters: max|session - ref| <= LOGIT_TOL *
      max(1, max|ref|). Both sides run the program's own model code, so
      this checks the bf16 precision and the kernel path, not the model's
      maths;
  (e) after adapt, the tenant's logits differ from the base model's.

Any failed check exits nonzero without the final line. The last line of
standard output is one JSON object naming the device JAX reports; it is
printed only on a TPU, after every check passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: One chip: 4 tenants at rank 8, 8 x 128-token ingest rows each, 2 fused
#: adapt epochs at 4 rows per tenant, serves of 5 requests (one per tenant
#: plus one base) with 128-token prompts and 32 new tokens at temperature 0.
SESSION_ARGV = [
    "--arch", "stablelm-1.6b", "--full", "--tenants", "4", "--rounds", "1",
    "--samples-per-round", "8", "--seq", "128", "--prompt-len", "128",
    "--gen", "32", "--adapt-epochs", "2", "--batch-per-tenant", "4",
    "--rank", "8", "--use-kernel", "--scheduler",
]

#: Four chips: the same session on the 2-D mesh (2 data groups, each one
#: backbone replica split over 2 model devices), held to ``repro.launch.run``'s
#: parity bar. That bar is one for float32 arithmetic, so the backbone runs
#: in float32 with float32 matmuls. The grouped kernels do not partition
#: over the model axis, so they are off here.
MESH_ARGV = [
    "--arch", "stablelm-1.6b", "--full", "--dtype", "float32",
    "--tenants", "4", "--rounds", "1",
    "--samples-per-round", "8", "--seq", "128", "--prompt-len", "128",
    "--gen", "32", "--adapt-epochs", "2", "--batch-per-tenant", "4",
    "--rank", "8", "--scheduler", "--mesh", "2x2", "--check-parity",
]

#: (c): a kernel and its oracle share bf16 operands and f32 accumulation;
#: they may differ by the bf16 rounding of the rank-R intermediate and of
#: the output, about 2^-8 relative each.
KERNEL_TOL = 2e-2
#: (d): a bf16 backbone against a float32 one; bf16 keeps 8 mantissa bits,
#: and the error grows over the residual stream's depth. On a TPU v5e the
#: session read 0.0307 against a logit scale of 6.37 (0.48 %).
LOGIT_TOL = 1.5e-2


@dataclasses.dataclass
class Check:
    name: str
    ok: bool
    detail: str


def _phase_times(labels, cold, warm) -> list[tuple[str, float, float]]:
    """(label, compile_s, run_s) per event, and per event kind."""
    rows = [(l, c - w, w) for l, c, w in zip(labels, cold, warm)]
    kinds: dict[str, list[float]] = {}
    for label, c, w in zip(labels, cold, warm):
        k = kinds.setdefault(label.split("/")[0] + " (all)", [0.0, 0.0])
        k[0] += c - w
        k[1] += w
    return rows + [(k, v[0], v[1]) for k, v in kinds.items()]


def _timed_twice(fn):
    """(result, compile_s, run_s): the first call compiles, the second is
    warm; both end once the result is ready."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    warm = time.perf_counter() - t0
    return out, cold - warm, warm


def session_checks(run_argv: list[str]) -> dict:
    """Run the session described by ``run_argv`` (``repro.launch.run``
    arguments; ``--scheduler`` and ``--use-kernel`` expected) twice and
    make checks (a)-(e) on the warm pass. Returns {"phases": [(label,
    compile_s, run_s)], "checks": [Check], "replay_equal": bool}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import lm_skiplora as SL
    from repro.core.lm_skiplora import quantize_int8
    from repro.kernels.skip_lora import ops
    from repro.kernels.skip_lora import quant as Q
    from repro.kernels.skip_lora import ref as R
    from repro.launch import run as RUN
    from repro.models.lm import lm_forward, model_dtype, readout

    session = RUN.build_session(RUN.parse_args(run_argv))
    args, cfg = session.args, session.cfg

    rt = session.make_runtime(1)
    first, cold = RUN.run_stream(session, rt)
    del rt
    gc.collect()
    rt = session.make_runtime(1)
    results, warm = RUN.run_stream(session, rt)
    phases = _phase_times(session.labels, cold, warm)
    replay_equal = all(
        np.array_equal(np.asarray(first[i]), np.asarray(results[i]))
        for i, label in enumerate(session.labels)
        if not label.startswith("adapt/")
    )
    checks = []

    # (a) every request completed with gen in-vocab tokens.
    serves = [i for i, l in enumerate(session.labels) if l.startswith("serve/")]
    bad = []
    for i in serves:
        toks = np.asarray(results[i])
        if toks.shape != (args.tenants + 1, args.gen) or not (
            (toks >= 0) & (toks < cfg.vocab_size)
        ).all():
            bad.append(f"{session.labels[i]} {toks.shape}")
    checks.append(Check(
        "a_requests", not bad,
        f"{len(serves)} serves x {args.tenants + 1} requests x {args.gen} "
        f"tokens in [0, {cfg.vocab_size})" + (f"; bad: {bad}" if bad else ""),
    ))

    # (b) finite adapt losses, last epoch below first, per tenant.
    adapt = next(i for i, l in enumerate(session.labels) if l.startswith("adapt/"))
    losses = results[adapt]["losses"]
    first_last = {
        t: (float(np.mean(losses[t][0])), float(np.mean(losses[t][-1])))
        for t in session.names
    }
    finite = all(np.isfinite(losses[t]).all() for t in session.names)
    falls = all(b < a for a, b in first_last.values())
    checks.append(Check(
        "b_adapt_losses", finite and falls,
        "first->last epoch mean: " + ", ".join(
            f"{t} {a:.4f}->{b:.4f}" for t, (a, b) in first_last.items()
        ) + ("" if finite else "; NON-FINITE"),
    ))

    # (c) each skip-LoRA kernel vs its oracle at this width, on the
    # session's own pool: serve over float / int8 / int4 / nf4 slots, adapt
    # over an int8 activation cache, and the single-adapter int8 cache path.
    who = [None] + session.names
    idx = rt.pool.lookup_local(0, who)
    pools = rt.pool.shard_pools(0)
    a_pool, b_pool = pools["A"], pools["B"]
    acts = jax.random.normal(
        jax.random.key(4), (cfg.n_layers, len(who), args.seq, cfg.d_model)
    ).astype(model_dtype(cfg))
    q_acts, s_acts = quantize_int8(acts)
    slot = idx[1]

    def fused_int8(use_kernel, q, s, a, b):
        if use_kernel:
            return ops.skip_lora_fused_int8(q, s, a, b)
        rows = lambda x: x.reshape(q.shape[0], -1, *x.shape[3:])
        return R.skip_lora_int8_fwd_ref(rows(q), rows(s), a, b).reshape(q.shape[1:])

    # name -> (fn(use_kernel, *arrays), arrays)
    cases = {
        "grouped": (
            lambda k, *xs: ops.skip_lora_grouped(*xs, use_kernel=k),
            (acts, a_pool, b_pool, idx)),
        "grouped_int8": (
            lambda k, *xs: ops.skip_lora_grouped_int8(*xs, use_kernel=k),
            (acts, *quantize_int8(a_pool), *quantize_int8(b_pool), idx)),
        "grouped_actint8": (
            lambda k, *xs: ops.skip_lora_grouped_train_int8(*xs, use_kernel=k),
            (q_acts, s_acts, a_pool, b_pool, idx)),
        "fused_int8": (
            fused_int8, (q_acts, s_acts, a_pool[slot], b_pool[slot])),
    }
    for kind in ("int4", "nf4"):
        cases[f"grouped_{kind}"] = (
            lambda k, *xs: ops.skip_lora_grouped_q4(*xs, use_kernel=k),
            (acts, *Q.quantize_q4(a_pool, kind), *Q.quantize_q4(b_pool, kind),
             Q.codebook(kind), idx),
        )
    kc = kr = 0.0
    errs, kernels_ok = [], True
    for name, (fn, xs) in cases.items():
        kern_fn = jax.jit(functools.partial(fn, True))
        kern, c_s, r_s = _timed_twice(lambda: kern_fn(*xs))
        kc, kr = kc + c_s, kr + r_s
        kern = np.asarray(kern, np.float32)
        oracle = np.asarray(jax.jit(functools.partial(fn, False))(*xs), np.float32)
        scale = float(np.max(np.abs(oracle)))
        kerr = float(np.max(np.abs(kern - oracle)))
        kernels_ok &= bool(np.isfinite(kerr) and scale > 0
                           and kerr <= KERNEL_TOL * scale)
        errs.append(f"{name} {kerr:.6g}/{scale:.6g}")
    phases.append(("check/kernels", kc, kr))
    checks.append(Check(
        "c_kernels_vs_oracles", kernels_ok,
        f"max|kernel-oracle| / max|oracle|, each <= {KERNEL_TOL}: "
        + ", ".join(errs),
    ))

    # (d) session logits vs a float32 jnp forward, same params + adapters.
    tenant = session.names[0]
    tokens, _ = session.tenant_batch(0, 0)
    got = np.asarray(rt.score(tenant, tokens), np.float32)
    payload = rt.pool.shards[rt.pool.shard_of(tenant)].slot_payload(tenant)
    adapters = SL.adapters_to_stack(
        {k: jnp.asarray(v, jnp.float32) for k, v in payload.items()}, cfg
    )
    cfg32 = dataclasses.replace(cfg, dtype="float32")

    @jax.jit
    def reference(params, adapters, tokens):
        out = lm_forward(params, cfg32, tokens, mode="train", adapters=adapters)
        return readout(params, cfg32, out["h"][:, -1:])

    def run_reference():
        with jax.default_matmul_precision("highest"):
            return reference(rt.params, adapters, tokens)

    ref, rc, rr = _timed_twice(run_reference)
    ref = np.asarray(ref, np.float32)
    phases.append(("check/reference", rc, rr))
    lscale = max(1.0, float(np.max(np.abs(ref))))
    lerr = float(np.max(np.abs(got - ref)))
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    checks.append(Check(
        "d_logits_vs_f32", np.isfinite(lerr) and lerr <= LOGIT_TOL * lscale,
        f"{tokens.shape[0]} rows: max|session-ref| {lerr:.6g} <= {LOGIT_TOL}"
        f" * {lscale:.6g}; top-1 agreement {agree:.3f}",
    ))

    # (e) the adapted tenant no longer serves the base model's logits.
    base = np.asarray(rt.score(None, tokens), np.float32)
    moved = float(np.max(np.abs(got - base)))
    checks.append(Check(
        "e_adapted_differs", np.isfinite(moved) and moved > 0,
        f"max|{tenant} - base| {moved:.6g} > 0",
    ))
    return {"phases": phases, "checks": checks, "replay_equal": replay_equal}


def mesh_parity(run_argv: list[str]) -> dict:
    """The 2-D mesh session of ``run_argv`` against its 1-device twin at
    ``repro.launch.run``'s parity bar, with float32 matmuls. The mesh
    session's results go to the host and its runtime is freed before the
    twin runs. Returns {"phases": [(label, wall s)], "memory": [(device,
    memory_stats)], "backbone": {device: bytes of the session's backbone
    replicas it holds}, "diffs", "measured": worst differences, "bar"}."""
    import jax

    from repro.launch import run as RUN

    with jax.default_matmul_precision("highest"):
        session = RUN.build_session(RUN.parse_args(run_argv))
        rt = session.make_runtime(session.args.devices)
        results, secs = RUN.run_stream(session, rt)
        memory = [
            (str(d), d.memory_stats() or {}) for d in rt.mesh.devices.ravel()
        ]
        # Backbone bytes each device holds of the session's replicas.
        backbone = {}
        for params in rt._shard_params:
            for x in jax.tree.leaves(params):
                for s in x.addressable_shards:
                    backbone[str(s.device)] = (
                        backbone.get(str(s.device), 0) + s.data.nbytes
                    )
        snap = RUN.parity_snapshot(session, rt, results)
        del rt, results
        gc.collect()
        twin = session.make_runtime(1)
        twin_results, twin_secs = RUN.run_stream(session, twin)
        twin_snap = RUN.parity_snapshot(session, twin, twin_results)
    phases = [(f"mesh {l}", s) for l, s in zip(session.labels, secs)]
    phases += [(f"twin {l}", s) for l, s in zip(session.labels, twin_secs)]
    diffs, measured = RUN.parity_diffs(session, snap, twin_snap)
    return {
        "phases": phases,
        "memory": memory,
        "backbone": backbone,
        "diffs": diffs,
        "measured": measured,
        "bar": RUN.parity_bar(session),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the 2x2 mesh session and its twin")
    opts = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this check runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch import enable_compile_cache
    from repro.launch import run as RUN

    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    run_argv = MESH_ARGV if opts.chips == 4 else SESSION_ARGV
    cache = enable_compile_cache(
        (RUN.mesh_dims(RUN.parse_args(run_argv)) or (1, 1))[1]
    )
    print(f"compile cache: {cache or 'off (programs span several devices)'}")

    t0 = time.perf_counter()
    if opts.chips == 4:
        out = mesh_parity(run_argv)
        for label, secs in out["phases"]:
            print(f"phase {label:<28s} wall_s={secs:.3f}")
        for name, stats in out["memory"]:
            print(f"memory {name}: bytes_in_use={stats.get('bytes_in_use')} "
                  f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
                  f"backbone_bytes={out['backbone'].get(name, 0)}")
        for k, v in out["measured"].items():
            print(f"parity {k}: {v:.6g}")
        ok = not out["diffs"]
        print(f"check parity_vs_1_device_twin: {'PASS' if ok else 'FAIL'} "
              f"({out['bar']})" + (f"; diffs {out['diffs']}" if not ok else ""))
    else:
        out = session_checks(run_argv)
        for label, comp, run_s in out["phases"]:
            print(f"phase {label:<28s} compile_s={comp:.3f} run_s={run_s:.3f}")
        print(f"replay: second pass equals first: {out['replay_equal']}")
        for c in out["checks"]:
            print(f"check {c.name}: {'PASS' if c.ok else 'FAIL'} ({c.detail})")
        ok = all(c.ok for c in out["checks"])
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    print(f"total_s: {time.perf_counter() - t0:.3f}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
