"""Interleaved continual-learning session CLI — the runtime's event loop,
now mesh-native and supervised.

The paper's deployment story end to end (DESIGN.md §9/§10): one
``SessionRuntime``, constructed over an explicit device mesh, processes an
interleaved stream of serve, ingest, and adapt events over a sharded
adapter pool and per-shard skip-cache engines. Each round, every tenant
(1) serves a mixed batch next to base-model traffic, (2) ingests freshly
"collected" samples — the populate forward that writes its cache partition
and returns logits, so ingestion is also a serving hit — and (3) runs a
grouped cached ``adapt`` whose write-back immediately changes what the
next serve returns.

  PYTHONPATH=src python -m repro.launch.run --arch stablelm-1.6b \
      --reduced --tenants 3 --rounds 2 --samples-per-round 4 --seq 16 \
      --gen 8 --adapt-epochs 2

Mesh + fault-tolerance controls:

  --devices N        run over an N-way data mesh (with JAX_PLATFORMS=cpu,
                     N forced host devices, set before JAX starts)
  --mesh DxM         2-D session mesh (DESIGN.md §14): D data groups, each
                     serving/adapting from ONE backbone replica TP-sharded
                     over M model devices; overrides --devices with D*M
  --pipeline-stages N  with --scheduler and --mesh DxM (N == M): admission
                     prefill runs as a microbatched N-stage pipeline over
                     the model-axis ring; decode stays on the TP path
  --check-parity     run the SAME event stream twice — on the N-device
                     mesh and on a 1-device mesh with the identical
                     logical shard layout — and compare adapters, adapt
                     losses, pool slot tables, and serve tokens: bitwise
                     on a data mesh, where placement is numerically free
                     (DESIGN.md §10); on a model axis, tokens and slot
                     tables exact and adapters/losses within rtol 1e-3 /
                     atol 1e-5 (DESIGN.md §14). The model-axis bar is one
                     for float32 arithmetic (--dtype float32; on a TPU,
                     with float32 matmul precision).
  --checkpoint-dir D run the event stream under a ``SessionSupervisor``:
                     checkpoint at every event boundary, restart after
                     failure with zero event replay.
  --inject-failure K raise inside event K on its first execution (crash
                     drill; requires --checkpoint-dir).
  --elastic-devices M after the injected failure, restart the session on
                     only M devices (elastic re-mesh: same logical shards,
                     fewer physical devices — the continuation is bitwise).

Prints per-event wall times and the runtime's path/tier counters; --json
dumps the same metrics machine-readably.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Callable


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--dtype", choices=["bfloat16", "float32"], default=None,
                    help="override the config's parameter/activation dtype")
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--samples-per-round", type=int, default=4)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--adapt-epochs", type=int, default=1)
    ap.add_argument("--batch-per-tenant", type=int, default=4)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--mode", default="full", choices=["full", "int8"])
    ap.add_argument("--pool-compress", choices=["int8", "int4", "nf4"],
                    default=None)
    ap.add_argument("--control", action="store_true",
                    help="enable the adapter control plane (DESIGN.md §13): "
                         "per-tenant shadow eval inside adapt, regression "
                         "gate on write-back, versioned slots with rollback")
    ap.add_argument("--control-threshold", type=float, default=0.0,
                    help="max tolerated held-out regression (post - pre) "
                         "before the gate fires")
    ap.add_argument("--control-mode", default="reject",
                    choices=["reject", "quarantine"],
                    help="what a gated write-back does to training state")
    ap.add_argument("--holdout-every", type=int, default=4,
                    help="every N-th ingested row per tenant is held out "
                         "for shadow eval")
    ap.add_argument("--history-depth", type=int, default=2,
                    help="previous adapter versions kept per tenant for "
                         "rollback")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--hbm-mb", type=float, default=0.0,
                    help="cache HBM budget in MiB; 0 = fully device-resident")
    ap.add_argument("--unroll", type=int, default=1)
    ap.add_argument("--devices", type=int, default=1,
                    help="data-mesh devices (forced on CPU via XLA_FLAGS)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="2-D session mesh, e.g. 2x2: D data groups, each "
                         "serving from ONE backbone replica TP-sharded over "
                         "M model devices (DESIGN.md §14). Overrides "
                         "--devices with D*M; M=1 is the data-only mesh.")
    ap.add_argument("--pipeline-stages", type=int, default=0, metavar="N",
                    help="pipeline the scheduler's admission prefill over N "
                         "stages (requires --mesh DxM with N == M and "
                         "--scheduler; decode stays on the TP path)")
    ap.add_argument("--shards", type=int, default=None,
                    help="logical shard count (default: --devices, or D "
                         "with --mesh DxM)")
    ap.add_argument("--check-parity", action="store_true",
                    help="sharded session vs 1-device same-layout twin: "
                         "bitwise on a data mesh; on a model axis, tokens "
                         "exact and adapters/losses within rtol 1e-3 / atol "
                         "1e-5, a bar for float32 arithmetic (DESIGN.md §14; "
                         "requires --devices >= 2)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="supervise the event stream with per-event "
                         "session checkpoints")
    ap.add_argument("--inject-failure", type=int, default=None, metavar="K",
                    help="crash inside event K once (requires "
                         "--checkpoint-dir)")
    ap.add_argument("--elastic-devices", type=int, default=None, metavar="M",
                    help="restart on only M devices after the injected "
                         "failure")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve through the continuous-batching request "
                         "scheduler (per-request admission + drain) instead "
                         "of pre-formed serve batches; ingest/adapt events "
                         "are unchanged")
    ap.add_argument("--sched-chunk", type=int, default=4,
                    help="decode steps per scheduler dispatch")
    ap.add_argument("--json", default=None, help="write metrics to this path")
    return ap.parse_args(argv)


@dataclasses.dataclass
class Session:
    """One configured session, built by ``build_session``: the backbone,
    the event stream in order (``labels[i]`` names ``events[i]``, a
    ``fn(runtime, i)``), and ``make_runtime(n_devices)``. ``main`` drives
    it; so does ``chip_smoke.py``. ``tenant_batch(round, t)`` regenerates
    the (tokens, labels) that tenant ``t`` ingests in that round."""

    args: argparse.Namespace
    cfg: Any
    sl: Any
    params: Any
    names: list
    events: list
    labels: list
    make_runtime: Callable[[int], Any]
    tenant_batch: Callable[[int, int], tuple]
    n_model: int
    n_shards: int
    control_cfg: Any = None


def mesh_dims(args: argparse.Namespace) -> tuple[int, int] | None:
    """(D, M) of ``--mesh DxM``, or None without it."""
    if not args.mesh:
        return None
    d, _, m = args.mesh.lower().partition("x")
    try:
        dims = (int(d), int(m or 1))
    except ValueError:
        raise SystemExit(f"--mesh wants DxM (e.g. 2x2), got {args.mesh!r}")
    if dims[0] < 1 or dims[1] < 1:
        raise SystemExit(f"--mesh axes must be >= 1, got {args.mesh!r}")
    return dims


def build_session(args: argparse.Namespace) -> Session:
    """Validate ``args`` and build the session they describe. On the CPU
    (``JAX_PLATFORMS=cpu``) ``--devices N > 1`` forces N host devices,
    which must happen before JAX starts its backend; nowhere else is that
    flag set, so a run that finds no chip never passes on fake devices."""
    mesh_dm = mesh_dims(args)
    if mesh_dm:
        args.devices = mesh_dm[0] * mesh_dm[1]
    n_model = mesh_dm[1] if mesh_dm else 1
    if args.pipeline_stages:
        if args.pipeline_stages != n_model or n_model < 2:
            raise SystemExit(
                "--pipeline-stages N repurposes the model axis as the "
                f"pipeline ring, so N must equal M of --mesh DxM (got "
                f"N={args.pipeline_stages}, M={n_model})"
            )
        if not args.scheduler:
            raise SystemExit(
                "--pipeline-stages pipelines the scheduler's admission "
                "prefill; add --scheduler"
            )
    if n_model > 1 and args.use_kernel:
        raise SystemExit(
            "grouped Pallas kernels do not partition over the model axis; "
            "drop --use-kernel for --mesh with M > 1"
        )
    if n_model > 1 and args.checkpoint_dir:
        raise SystemExit(
            "supervised restart re-meshes along the data axis only; "
            "--checkpoint-dir is not supported with --mesh M > 1 yet"
        )
    if (
        args.devices > 1
        and os.environ.get("JAX_PLATFORMS") == "cpu"
        and "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
    ):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )
    if args.check_parity and args.devices < 2:
        raise SystemExit(
            "--check-parity compares an N-device mesh against its 1-device "
            "twin; for the single-device session's bitwise bar against the "
            "offline trainer use launch/fleet.py --devices 1 --check-parity"
        )
    if args.inject_failure is not None and not args.checkpoint_dir:
        raise SystemExit("--inject-failure requires --checkpoint-dir")

    import jax
    import numpy as np

    from repro.configs import get_config, reduce_config
    from repro.core import lm_skiplora as SL
    from repro.core.control_plane import ControlConfig
    from repro.core.runtime import SessionRuntime
    from repro.models.lm import init_lm
    from repro.runtime.sharding import make_mesh

    if len(jax.devices()) < args.devices:
        raise SystemExit(
            f"need {args.devices} devices, have {len(jax.devices())} (on the "
            "CPU, run with JAX_PLATFORMS=cpu to force host devices)"
        )
    n_shards = (
        args.shards if args.shards is not None
        else (mesh_dm[0] if mesh_dm else args.devices)
    )
    if args.tenants % n_shards:
        raise SystemExit(
            f"--tenants {args.tenants} must divide over {n_shards} shards"
        )

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    sl = SL.SkipLoRAConfig(rank=args.rank, mode=args.mode,
                           cache_dtype="float32",
                           use_fused_kernel=args.use_kernel)
    params = init_lm(jax.random.key(0), cfg)
    control_cfg = (
        ControlConfig(
            holdout_every=args.holdout_every,
            threshold=args.control_threshold,
            mode=args.control_mode,
            history_depth=args.history_depth,
        )
        if args.control else None
    )
    names = [f"tenant-{t}" for t in range(args.tenants)]
    prompts = jax.random.randint(
        jax.random.key(1), (args.tenants + 1, args.prompt_len), 0, cfg.vocab_size
    )

    def make_runtime(n_devices: int) -> SessionRuntime:
        # The 1-device parity twin always runs the data-only layout: device
        # placement (including the TP split) must be numerically free.
        if n_model > 1 and n_devices == args.devices:
            mesh = make_mesh(
                mesh_dm, ("data", "model"), devices=jax.devices()[:n_devices]
            )
            stages = args.pipeline_stages
        else:
            mesh = make_mesh(
                (n_devices,), ("data",), devices=jax.devices()[:n_devices]
            )
            stages = 0
        return SessionRuntime(
            cfg, sl, params,
            max_tenants=args.tenants,
            samples_per_tenant=args.rounds * args.samples_per_round,
            seq=args.seq, lr=args.lr, use_kernel=args.use_kernel,
            pool_compress=args.pool_compress,
            hbm_budget_bytes=(int(args.hbm_mb * 2**20) if args.hbm_mb > 0 else None),
            mesh=mesh, placement_shards=n_shards, control=control_cfg,
            pipeline_stages=stages,
        )

    # ---- the event stream: one closure per serve / ingest / adapt ---------
    # Per-tenant sample streams are derived from (round, tenant), NOT from a
    # carried RNG, so a restarted session regenerates identical batches.
    def tenant_batch(rnd: int, t: int):
        k1, k2 = jax.random.split(jax.random.fold_in(jax.random.key(2), rnd * args.tenants + t))
        toks = jax.random.randint(
            k1, (args.samples_per_round, args.seq), 0, cfg.vocab_size
        )
        labs = jax.random.randint(
            k2, (args.samples_per_round, args.seq), 0, cfg.vocab_size
        )
        return toks, labs

    def sched_serve(rt: SessionRuntime, who):
        """One serve event through the request scheduler: enqueue each row
        as its own request (staggered admission, recycled rows), drain, and
        stack the per-request token streams back into the (B, gen) layout
        the batch path returns — so --check-parity compares unchanged."""
        if rt._scheduler is None:
            rt.attach_scheduler(
                max_batch=args.tenants + 1, max_prompt=args.prompt_len,
                max_new_cap=args.gen, chunk=args.sched_chunk,
                admit_bucket=min(2, args.tenants + 1),
            )
        reqs = [
            rt.enqueue_serve(t, np.asarray(prompts[j]), max_new=args.gen)
            for j, t in enumerate(who)
        ]
        rt.drain()
        return jax.numpy.stack([jax.numpy.asarray(r.result()) for r in reqs])

    def serve_event(rt, who):
        if args.scheduler:
            return sched_serve(rt, who)
        return rt.serve(who, prompts, max_new=args.gen, unroll=args.unroll)

    events, labels = [], []

    def ev(label, fn):
        events.append(fn)
        labels.append(label)

    ev("serve/base", lambda rt, i: serve_event(
        rt, [None] * (args.tenants + 1)
    ))
    for rnd in range(args.rounds):
        for t, name in enumerate(names):
            ev(f"ingest/{name}/r{rnd}", lambda rt, i, rnd=rnd, t=t, name=name:
               rt.ingest(name, *tenant_batch(rnd, t)))
        ev(f"adapt/r{rnd}", lambda rt, i: rt.adapt(
            names, epochs=args.adapt_epochs,
            batch_per_tenant=args.batch_per_tenant, key=jax.random.key(3),
        ))
        ev(f"serve/mixed/r{rnd}", lambda rt, i: serve_event(
            rt, [None] + names
        ))
    return Session(
        args=args, cfg=cfg, sl=sl, params=params, names=names, events=events,
        labels=labels, make_runtime=make_runtime, tenant_batch=tenant_batch,
        n_model=n_model, n_shards=n_shards, control_cfg=control_cfg,
    )


def run_stream(session: Session, rt) -> tuple[dict[int, Any], list[float]]:
    """Run the event stream on ``rt``; each event's time ends once every
    array it returned is ready. Returns (results by event index, seconds
    per event)."""
    import jax

    results, seconds = {}, []
    for i, (fn, label) in enumerate(zip(session.events, session.labels)):
        t0 = time.perf_counter()
        out = fn(rt, i)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        print(f"{label:<24s} {dt:6.2f}s")
        results[i] = out
        seconds.append(dt)
    return results, seconds


def parity_snapshot(session: Session, rt, results) -> dict:
    """What ``parity_diffs`` compares, pulled to the host: each tenant's
    adapters, each adapt event's losses, each serve's tokens, and the
    pool's slot table. Taken before the twin runs, it lets the caller free
    the sharded runtime first."""
    import numpy as np

    snap = {
        "adapters": {
            name: {k: np.asarray(rt.tenant(name).adapters[k]) for k in ("A", "B")}
            for name in session.names
        },
        "losses": {},
        "tokens": {},
        "slots": rt.pool.slot_table(),
    }
    for i, label in enumerate(session.labels):
        if label.startswith("adapt/") and isinstance(results.get(i), dict):
            snap["losses"][label] = {
                name: np.asarray(results[i]["losses"][name])
                for name in session.names
            }
        if label.startswith("serve/") and i in results:
            snap["tokens"][label] = np.asarray(results[i])
    return snap


def parity_diffs(
    session: Session, snap: dict, twin_snap: dict
) -> tuple[list[str], dict[str, float]]:
    """(what differs, measured worst cases) between a session's
    ``parity_snapshot`` and its 1-device twin's, after the same events.

    Placement along the DATA axis is numerically free: adapters, losses and
    tokens must be bitwise. The MODEL axis reorders float partial sums (TP
    contractions), so adapters and losses there are held to rtol 1e-3 /
    atol 1e-5, while temp-0 tokens and slot tables stay exact. That bar is
    one for float32 arithmetic; a bfloat16 backbone rounds each reordered
    sum to 8 mantissa bits and does not meet it (DESIGN.md §14)."""
    import numpy as np

    diffs: list[str] = []
    tp = session.n_model > 1

    def outside(x, y) -> int:
        """Elements of x outside the bar around y."""
        if tp:
            return int(np.sum(~np.isclose(x, y, rtol=1e-3, atol=1e-5)))
        return int(np.sum(x != y))

    def max_abs(pairs) -> float:
        return max((float(np.max(np.abs(x - y))) for x, y in pairs), default=0.0)

    adapter_pairs, loss_pairs, token_same = [], [], []
    adapters_outside = 0
    for name in session.names:
        for leaf in ("A", "B"):
            x = snap["adapters"][name][leaf]
            y = twin_snap["adapters"][name][leaf]
            adapter_pairs.append((x, y))
            n = outside(x, y)
            adapters_outside += n
            if n:
                diffs.append(f"adapters[{name}][{leaf}]")
    for label, losses in snap["losses"].items():
        for name in session.names:
            x, y = losses[name], twin_snap["losses"][label][name]
            loss_pairs.append((x, y))
            if outside(x, y):
                diffs.append(f"losses[{label}][{name}]")
    for label, toks in snap["tokens"].items():
        same = toks == twin_snap["tokens"][label]
        token_same.append(same.ravel())
        if not same.all():
            diffs.append(f"tokens[{label}]")
    if snap["slots"] != twin_snap["slots"]:
        diffs.append("pool slot tables")
    measured = {
        "adapters_max_abs_diff": max_abs(adapter_pairs),
        "adapter_elements_outside_bar": float(adapters_outside),
        "losses_max_abs_diff": max_abs(loss_pairs),
    }
    if token_same:
        measured["token_agreement"] = float(np.mean(np.concatenate(token_same)))
    return diffs, measured


def parity_bar(session: Session) -> str:
    if session.n_model == 1:
        return "bitwise (adapters, losses, tokens, slot tables)"
    return ("tokens and slot tables exact; adapters/losses within rtol 1e-3 "
            "/ atol 1e-5")


def main(argv=None) -> dict:
    args = parse_args(argv)
    session = build_session(args)

    import jax
    import numpy as np

    from repro.core.runtime import SessionRuntime
    from repro.runtime.fault import SessionSupervisor, elastic_session_mesh

    cfg, params, n_shards = session.cfg, session.params, session.n_shards

    t_session0 = time.perf_counter()
    if args.checkpoint_dir:
        # ---- supervised session: checkpoint/restart at event boundaries --
        healthy = {"n": args.devices}
        fail_at = {"k": args.inject_failure}

        def boot_runtime():
            # Elastic re-mesh over whatever survived: the session's logical
            # shard layout is a checkpoint property; only placement changes.
            mesh = elastic_session_mesh(jax.devices()[: healthy["n"]])
            return SessionRuntime(
                cfg, session.sl, params,
                max_tenants=args.tenants,
                samples_per_tenant=args.rounds * args.samples_per_round,
                seq=args.seq, lr=args.lr, use_kernel=args.use_kernel,
                pool_compress=args.pool_compress,
                hbm_budget_bytes=(
                    int(args.hbm_mb * 2**20) if args.hbm_mb > 0 else None
                ),
                mesh=mesh, placement_shards=n_shards,
                control=session.control_cfg,
            )

        def wrap(i, fn):
            def run_event(rt, idx):
                if fail_at["k"] == idx:
                    fail_at["k"] = None  # crash once
                    if args.elastic_devices is not None:
                        healthy["n"] = args.elastic_devices  # hosts died
                    raise RuntimeError(f"injected failure in event {idx}")
                return fn(rt, idx)
            return run_event

        sup = SessionSupervisor(args.checkpoint_dir, save_every=1)
        rt, info = sup.run(
            boot_runtime, [wrap(i, fn) for i, fn in enumerate(session.events)]
        )
        print(f"supervised: {len(session.events)} events, "
              f"{info['restarts']} restarts, "
              f"resumed at event {info['resumed_at']}, "
              f"{len(info['results'])} executed this incarnation "
              f"(zero replay of completed events)")
        results = info["results"]
        timings: dict[str, float] = {}
    else:
        rt = session.make_runtime(args.devices)
        results, seconds = run_stream(session, rt)
        timings = {}
        for label, dt in zip(session.labels, seconds):
            kind = label.split("/")[0]
            timings[kind] = timings.get(kind, 0.0) + dt
    session_s = time.perf_counter() - t_session0

    stats = rt.stats()
    # Backbone memory accounting: total param bytes vs the peak any single
    # device actually holds of shard 0's replica — 1.0x when replicated,
    # ~Mx smaller per device on a --mesh DxM TP split.
    bytes_total = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(params)
    )
    bytes_peak = max(
        sum(
            s.data.nbytes
            for x in jax.tree.leaves(rt._shard_params[0])
            for s in x.addressable_shards
            if s.device == d
        )
        for d in rt.mesh.devices.ravel()
    )
    metrics = {
        **{f"time/{k}_s": v for k, v in timings.items()},
        "session/tenants_per_s": args.tenants * args.rounds / session_s,
        "session/wall_s": session_s,
        "session/devices": float(args.devices),
        "session/shards": float(n_shards),
        "session/model_parallel": float(rt.model_parallel),
        "session/pipeline_stages": float(rt.pipeline_stages),
        "session/backbone_bytes_total": float(bytes_total),
        "session/backbone_bytes_per_device_peak": float(bytes_peak),
        **stats,
    }
    cm = rt.control_metrics()
    if cm is not None:
        # Scalar gate counters flatten next to the runtime counters; the
        # full per-tenant ledger (eval deltas, decisions) nests under
        # "control" in the JSON dump.
        for k in ("accepted", "rejected", "quarantined", "rollbacks"):
            metrics[f"control/{k}"] = float(cm[k])
        metrics["control"] = cm
    print(f"\nsession: {args.tenants} tenants x {args.rounds} rounds on "
          f"{args.devices} device(s) / {n_shards} shard(s) in "
          f"{session_s:.2f}s ({metrics['session/tenants_per_s']:.2f} "
          f"tenant-rounds/s)")
    for k in sorted(stats):
        print(f"  {k} = {stats[k]:.3f}")

    if args.check_parity:
        print("\n--check-parity: replaying on the 1-device same-layout twin")
        snap = parity_snapshot(session, rt, results)
        twin = session.make_runtime(1)
        twin_results, _ = run_stream(session, twin)
        diffs, measured = parity_diffs(
            session, snap, parity_snapshot(session, twin, twin_results)
        )
        metrics["parity/diffs"] = float(len(diffs))
        for k, v in measured.items():
            metrics[f"parity/{k}"] = v
            print(f"  parity {k} = {v:.6g}")
        if diffs:
            raise SystemExit(f"sharded/twin parity broken: {diffs}")
        print(f"parity OK: {args.devices}-device session == 1-device twin "
              f"— {parity_bar(session)}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(metrics, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return metrics


if __name__ == "__main__":
    from repro.launch import enable_compile_cache

    enable_compile_cache((mesh_dims(parse_args()) or (1, 1))[1])
    main()
