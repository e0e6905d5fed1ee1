"""Session-runtime benchmarks: routed-serve overhead + interleaved session.

Two claims of the unified runtime (DESIGN.md §9), measured:

- **Routed decode overhead**: ``SessionRuntime.serve`` routes a mixed
  batch through the *same* compiled decode-scan entries as calling
  ``generate_grouped`` directly (the shared compiled-fn cache), so the
  runtime may add only a pool lookup and Python routing. The §9 bar is
  runtime-routed throughput within 10% of the direct PR 2 path on the same
  shapes; ``routed_overhead_x`` is the measured ratio.
- **Interleaved session throughput**: the full continual loop — serve,
  ingest (populate forward + logits back), grouped adapt, serve again —
  in tenant-rounds/sec, with the engine/pool counters that show the cache
  tiers and path selection doing their jobs.

Oracle (jnp) kernel path on CPU, like the other benches — interpret-mode
Pallas timing is correctness-grade only (see ``lm_bench.kernel_vs_einsum``).

The sharded section (``python -m benchmarks.runtime_bench --devices N
--json BENCH_runtime_sharded.json``) runs the SAME interleaved session on
an N-way forced-host-device mesh and on its 1-device same-layout twin,
reporting tenant-rounds/s for both plus the twin-parity max-abs-diff
(must be 0.0 — DESIGN.md §10). Forced CPU "devices" share the same cores,
so the ratio measures dispatch/overlap overhead, not real DP speedup; the
numbers are honest about that.

The 2-D section (``--mesh2d --devices M --json BENCH_runtime_2d.json``)
instead measures the big-backbone story on a ``(data=1, model=M)`` mesh:
per-device peak backbone bytes vs the replicated baseline (gate >= 0.8*M),
temp-0 serve token parity vs the 1-device twin (exact), and pipelined
scheduler admission (``pipeline_stages=M``) against the plain 2-D path
next to ``bubble_fraction``'s prediction (DESIGN.md §14).
"""

from __future__ import annotations

# The sharded section needs the forced device count set BEFORE the first
# jax import (the dryrun.py/fleet.py trick), so peek at argv when invoked
# as a script.
import os
import sys

def _peek_devices(argv: list[str]) -> str | None:
    for i, arg in enumerate(argv):
        if arg == "--devices" and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith("--devices="):
            return arg.split("=", 1)[1]
    return None


if __name__ == "__main__":
    _n = _peek_devices(sys.argv)
    if _n and "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={_n} "
            + os.environ.get("XLA_FLAGS", "")
        )

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core import lm_skiplora as SL
from repro.core.runtime import SessionRuntime, generate_grouped
from repro.launch.flops import model_flops
from repro.launch.hlo_analysis import analyze_collectives, analyze_dot_flops
from repro.launch.roofline import HBM_BW, PEAK_FLOPS
from repro.models.lm import init_lm, init_serve_caches, serve_decode_grouped, serve_prefill_grouped
from repro.runtime.sharding import make_mesh


def _time(fn, repeats: int = 5) -> float:
    jax.block_until_ready(fn())  # compile / warm caches
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def dispatch_cost(fn, *args) -> dict[str, float]:
    """Compile ``fn(*args)`` and report its static cost model: HLO dot
    FLOPs (launch.hlo_analysis, loop-multiplied), XLA's own flops/bytes
    estimate, collective bytes, and the roofline time bounds those imply."""
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    dot = analyze_dot_flops(hlo)
    coll = analyze_collectives(hlo)
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    return {
        "dot_flops": dot,
        "xla_flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": bytes_accessed,
        "collective_bytes": float(coll.total_bytes),
        "roofline_compute_s": dot / PEAK_FLOPS,
        "roofline_memory_s": bytes_accessed / HBM_BW,
    }


def dispatch_cost_rows(
    arch: str, cfg, params, prompts, pools, idx, *, b: int, prompt: int
) -> list[tuple[str, float]]:
    """Per-dispatch FLOPs + bytes columns for the two serve dispatches the
    scheduler lives in (grouped prefill, one grouped decode step), plus the
    analytic MODEL_FLOPS so the JSON shows the HLO-vs-model ratio."""
    caches = init_serve_caches(cfg, b, prompt + 8)

    def _prefill(p, tk, c, pl_a, pl_b, ix):
        return serve_prefill_grouped(
            p, cfg, tk, c, {"A": pl_a, "B": pl_b}, ix, use_kernel=False
        )

    def _decode(p, tok, pos, c, pl_a, pl_b, ix):
        return serve_decode_grouped(
            p, cfg, tok, pos, c, {"A": pl_a, "B": pl_b}, ix, use_kernel=False
        )

    tok1 = prompts[:, -1:]
    pos = jnp.asarray(prompt, jnp.int32)
    costs = {
        "prefill": dispatch_cost(
            _prefill, params, prompts, caches, pools["A"], pools["B"], idx
        ),
        "decode_step": dispatch_cost(
            _decode, params, tok1, pos, caches, pools["A"], pools["B"], idx
        ),
    }
    rows = [
        (f"runtime/{arch}/{disp}/{col}", val)
        for disp, cost in costs.items()
        for col, val in cost.items()
    ]
    for disp, step in (("prefill", "prefill"), ("decode_step", "decode")):
        mf = model_flops(cfg, (b, prompt), step)
        rows.append((f"runtime/{arch}/{disp}/model_flops", mf))
        hlo_f = costs[disp]["dot_flops"]
        if hlo_f > 0:
            rows.append((f"runtime/{arch}/{disp}/hlo_vs_model_x", hlo_f / mf))
    return rows


def _session(cfg, sl, params, n_tenants: int, spt: int, seq: int) -> SessionRuntime:
    return SessionRuntime(
        cfg, sl, params, max_tenants=n_tenants, samples_per_tenant=spt,
        seq=seq, lr=1e-2, use_kernel=False,
    )


def runtime_session(
    arch: str = "stablelm-1.6b",
    *,
    b: int = 4,
    prompt: int = 16,
    gen: int = 32,
    n_tenants: int = 3,
    rank: int = 8,
    n_per: int = 8,
    seq: int = 16,
    adapt_epochs: int = 2,
    unroll: int = 8,
    quick: bool = False,
) -> list[tuple[str, float]]:
    if quick:
        gen, adapt_epochs = 8, 1
    cfg = reduce_config(get_config(arch))
    sl = SL.SkipLoRAConfig(rank=rank, mode="full", cache_dtype="float32")
    params = init_lm(jax.random.key(0), cfg)
    prompts = jax.random.randint(
        jax.random.key(1), (b, prompt), 0, cfg.vocab_size
    )

    # -- routed serve vs direct generate_grouped on identical shapes --------
    rt = _session(cfg, sl, params, n_tenants, n_per, seq)
    names = [f"u{t}" for t in range(n_tenants)]
    for t, name in enumerate(names):
        ad = SL.init_adapters(jax.random.key(10 + t), cfg, sl)
        ad["B"] = jax.random.normal(jax.random.key(20 + t), ad["B"].shape) * 0.02
        rt.pool.register(name, ad)
    who = [None] + [names[i % n_tenants] for i in range(1, b)]
    idx = rt.pool.lookup(who)
    pools = rt.pool.pools()

    t_direct = _time(lambda: generate_grouped(
        params, cfg, prompts, pools, idx, max_new=gen, use_kernel=False,
        unroll=unroll,
    ))
    t_routed = _time(lambda: rt.serve(
        who, prompts, max_new=gen, unroll=unroll,
    ))
    toks = b * gen

    # -- static per-dispatch cost columns (launch.* cost models) ------------
    cost_rows = dispatch_cost_rows(
        arch, cfg, params, prompts, pools, idx, b=b, prompt=prompt
    )

    # -- interleaved session: serve -> ingest -> adapt -> serve -------------
    rt2 = _session(cfg, sl, params, n_tenants, n_per, seq)
    rng = jax.random.key(2)

    def session():
        # One continual round per tenant: serve, ingest (first trip fills
        # the partition; ingest cost then lives in session_cold_s), grouped
        # adapt, serve the freshly written-back slots.
        nonlocal rng
        rt2.serve([None] * b, prompts, max_new=gen, unroll=unroll)
        for name in names:
            if name in rt2._tenants and rt2.tenant(name).n_ingested >= n_per:
                continue
            rng, k1, k2 = jax.random.split(rng, 3)
            toks_in = jax.random.randint(k1, (n_per, seq), 0, cfg.vocab_size)
            labs = jax.random.randint(k2, (n_per, seq), 0, cfg.vocab_size)
            rt2.ingest(name, toks_in, labs)
        out = rt2.adapt(names, epochs=adapt_epochs, batch_per_tenant=4,
                        key=jax.random.key(3))
        rt2.serve([None] + who[1:], prompts, max_new=gen, unroll=unroll)
        return out["losses"][names[0]]

    t0 = time.perf_counter()
    session()  # compile + populate trip
    t_cold = time.perf_counter() - t0
    t_warm = _time(session, repeats=3)

    st = rt2.engine.stats
    return [
        (f"runtime/{arch}/direct_grouped_tok_s", toks / t_direct),
        (f"runtime/{arch}/routed_serve_tok_s", toks / t_routed),
        (f"runtime/{arch}/routed_overhead_x", t_routed / t_direct),
        (f"runtime/{arch}/session_cold_s", t_cold),
        (f"runtime/{arch}/session_tenant_rounds_per_s", n_tenants / t_warm),
        (f"runtime/{arch}/cache_hbm_hit_rate", st.hbm_hit_rate()),
        (f"runtime/{arch}/cache_spills", float(st.spills)),
        (f"runtime/{arch}/pool_tenants", float(len(rt2.pool))),
        (f"runtime/{arch}/pool_MiB", rt2.pool.nbytes() / 2**20),
        (f"runtime/{arch}/adapt_epochs", float(adapt_epochs)),
    ] + cost_rows


# ---------------------------------------------------------------------------
# Sharded section: mesh-native session vs its 1-device same-layout twin
# ---------------------------------------------------------------------------


def runtime_sharded(
    arch: str = "stablelm-1.6b",
    *,
    devices: int = 4,
    n_per: int = 8,
    seq: int = 16,
    bpt: int = 4,
    adapt_epochs: int = 2,
    rounds: int = 2,
    quick: bool = False,
) -> list[tuple[str, float]]:
    """One tenant per shard per device; the same event stream on the
    N-device mesh and the 1-device twin with identical logical layout.
    Twin parity (adapters) must be exactly 0.0."""
    if quick:
        adapt_epochs, rounds = 1, 1
    n_tenants = devices
    n_dev = min(devices, len(jax.devices()))
    cfg = reduce_config(get_config(arch))
    sl = SL.SkipLoRAConfig(rank=8, mode="full", cache_dtype="float32")
    params = init_lm(jax.random.key(0), cfg)
    tokens = jax.random.randint(
        jax.random.key(1), (n_tenants, rounds * n_per, seq), 0, cfg.vocab_size
    )
    labels = jax.random.randint(
        jax.random.key(2), (n_tenants, rounds * n_per, seq), 0, cfg.vocab_size
    )

    def session(n_devices: int):
        mesh = make_mesh(
            (n_devices,), ("data",), devices=jax.devices()[:n_devices]
        )
        rt = SessionRuntime(
            cfg, sl, params, max_tenants=n_tenants,
            samples_per_tenant=rounds * n_per, seq=seq, lr=1e-2,
            use_kernel=False, mesh=mesh, placement_shards=devices,
        )
        t0 = time.perf_counter()
        for rnd in range(rounds):
            for t in range(n_tenants):
                rt.ingest(f"u{t}", tokens[t, rnd * n_per:(rnd + 1) * n_per],
                          labels[t, rnd * n_per:(rnd + 1) * n_per])
            rt.adapt(epochs=adapt_epochs, batch_per_tenant=bpt,
                     key=jax.random.key(3))
        cold = time.perf_counter() - t0
        # Warm adapt epochs only (the steady state the mesh buys).
        t0 = time.perf_counter()
        rt.adapt(epochs=adapt_epochs, batch_per_tenant=bpt)
        warm = time.perf_counter() - t0
        return rt, cold, warm

    rt_mesh, cold_mesh, warm_mesh = session(n_dev)
    rt_twin, cold_twin, warm_twin = session(1)
    parity = max(
        float(np.max(np.abs(
            np.asarray(rt_mesh.tenant(f"u{t}").adapters[k])
            - np.asarray(rt_twin.tenant(f"u{t}").adapters[k])
        )))
        for t in range(n_tenants) for k in ("A", "B")
    )
    return [
        (f"runtime_sharded/{arch}/devices", float(n_dev)),
        (f"runtime_sharded/{arch}/shards", float(devices)),
        (f"runtime_sharded/{arch}/tenants", float(n_tenants)),
        (f"runtime_sharded/{arch}/session_cold_s", cold_mesh),
        (f"runtime_sharded/{arch}/adapt_warm_s", warm_mesh),
        (f"runtime_sharded/{arch}/adapt_warm_twin_1dev_s", warm_twin),
        (f"runtime_sharded/{arch}/adapt_tenants_per_s", n_tenants / warm_mesh),
        (f"runtime_sharded/{arch}/twin_parity_max_abs_diff", parity),
    ]


# ---------------------------------------------------------------------------
# 2-D section: one TP-sharded backbone on (data=1, model=M) vs replication
# ---------------------------------------------------------------------------


def runtime_2d(
    arch: str = "stablelm-1.6b",
    *,
    devices: int = 4,
    b: int = 4,
    prompt: int = 8,
    gen: int = 16,
    n_per: int = 4,
    seq: int = 8,
    quick: bool = False,
) -> list[tuple[str, float]]:
    """The big-backbone serving claim (DESIGN.md §14), measured on a
    ``(data=1, model=M)`` forced-host-device mesh against the replicated
    1-device twin running the same event stream:

      - ``backbone_bytes_ratio``: replicated param bytes over the peak
        per-device share of the TP-sharded replica — the reason to go 2-D.
        Gate: >= 0.8*M (tables and attention/FFN weights shard; norms and
        small biases replicate, hence the 0.8 slack).
      - ``serve_parity``: temp-0 serve tokens of a mixed base/adapter
        batch must match the twin exactly (GSPMD placement is numerically
        free at the dispatch granularity we compile).
      - ``pipe_wall_vs_bubble``: admission through the pipelined prefill
        (``pipeline_stages=M``, microbatched scheduler admission) vs the
        plain 2-D path on a prefill-heavy pass, next to ``bubble_fraction``'s
        prediction. Forced CPU devices share cores, so the wall gate is
        slack (1.5x over the bubble-adjusted bound), but pipelined tokens
        must equal the plain path bitwise.
    """
    import dataclasses

    from repro.runtime.pipeline_par import bubble_fraction

    if quick:
        gen = 8
    n_model = min(devices, len(jax.devices()))
    # One layer per pipeline stage; the reduced vocab (503) is deliberately
    # prime, but the bytes-ratio gate is *about* table sharding, so give TP
    # a divisible vocab.
    cfg = reduce_config(get_config(arch), n_periods=n_model)
    cfg = dataclasses.replace(cfg, vocab_size=512)
    sl = SL.SkipLoRAConfig(rank=4, mode="full", cache_dtype="float32")
    params = init_lm(jax.random.key(0), cfg)
    names = ["a", "b", "c"]
    prompts = jax.random.randint(jax.random.key(1), (b, prompt), 0, cfg.vocab_size)
    toks_in = jax.random.randint(jax.random.key(2), (n_per, seq), 0, cfg.vocab_size)
    labs_in = jax.random.randint(jax.random.key(3), (n_per, seq), 0, cfg.vocab_size)

    def session(mesh=None, pipeline_stages=0):
        rt = SessionRuntime(
            cfg, sl, params, max_tenants=len(names), samples_per_tenant=n_per,
            seq=seq, lr=1e-2, use_kernel=False, mesh=mesh, placement_shards=1,
            seed=0, pipeline_stages=pipeline_stages,
        )
        for name in names:
            rt.ingest(name, toks_in, labs_in)
        rt.adapt(names, epochs=1, key=jax.random.key(4))
        return rt

    mesh = make_mesh(
        (1, n_model), ("data", "model"), devices=jax.devices()[:n_model]
    )
    rt1 = session()
    rt2 = session(mesh)
    who = [None] + names[: b - 1]

    tok1 = rt1.serve(who, prompts, max_new=gen)
    tok2 = rt2.serve(who, prompts, max_new=gen)
    serve_parity = bool(np.array_equal(np.asarray(tok1), np.asarray(tok2)))
    t1 = _time(lambda: rt1.serve(who, prompts, max_new=gen), repeats=3)
    t2 = _time(lambda: rt2.serve(who, prompts, max_new=gen), repeats=3)
    toks = b * gen

    # Peak per-device backbone bytes: the replicated baseline holds every
    # param on its device; the 2-D replica's device share is read off the
    # committed arrays' addressable shards.
    total = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(params)
    )
    per_dev = max(
        sum(
            s.data.nbytes
            for x in jax.tree.leaves(rt2._shard_params[0])
            for s in x.addressable_shards
            if s.device == d
        )
        for d in mesh.devices.ravel()
    )
    bytes_ratio = total / per_dev

    # Pipelined admission vs the plain 2-D path on a prefill-heavy pass
    # (tiny decode budget, chunk covering it in one dispatch).
    rtp = session(mesh, pipeline_stages=n_model)
    s2 = rt2.attach_scheduler(
        max_batch=b, max_prompt=prompt, max_new_cap=gen, admit_bucket=b,
        chunk=gen,
    )
    sp = rtp.attach_scheduler(
        max_batch=b, max_prompt=prompt, max_new_cap=gen, admit_bucket=b,
        chunk=gen, microbatch=1,
    )
    bubble = bubble_fraction(sp.n_micro, n_model)
    assert abs(sp.predicted_bubble() - bubble) < 1e-12

    def sched_pass(rt):
        reqs = [
            rt.enqueue_serve(who[j], np.asarray(prompts[j]), max_new=4)
            for j in range(b)
        ]
        rt.drain()
        return [r.result().tolist() for r in reqs]

    toks_plain = sched_pass(rt2)   # compile trip
    toks_pipe = sched_pass(rtp)
    pipe_parity = toks_plain == toks_pipe
    t_plain = _time(lambda: sched_pass(rt2), repeats=3)
    t_pipe = _time(lambda: sched_pass(rtp), repeats=3)

    return [
        (f"runtime_2d/{arch}/model_parallel", float(n_model)),
        (f"runtime_2d/{arch}/backbone_bytes_total", float(total)),
        (f"runtime_2d/{arch}/backbone_bytes_per_device_peak", float(per_dev)),
        (f"runtime_2d/{arch}/backbone_bytes_ratio", bytes_ratio),
        (f"runtime_2d/{arch}/serve_tok_s_1dev", toks / t1),
        (f"runtime_2d/{arch}/serve_tok_s_2d", toks / t2),
        (f"runtime_2d/{arch}/serve_parity", 1.0 if serve_parity else 0.0),
        (f"runtime_2d/{arch}/pipe_bubble_predicted", bubble),
        (f"runtime_2d/{arch}/pipe_n_micro", float(sp.n_micro)),
        (f"runtime_2d/{arch}/sched_pass_plain_s", t_plain),
        (f"runtime_2d/{arch}/sched_pass_pipe_s", t_pipe),
        (f"runtime_2d/{arch}/pipe_wall_ratio", t_pipe / t_plain),
        (f"runtime_2d/{arch}/pipe_wall_bound", (1.0 + bubble) * 1.5),
        (f"runtime_2d/{arch}/pipe_parity", 1.0 if pipe_parity else 0.0),
    ]


def main(argv=None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--mesh2d", action="store_true",
                    help="run the (data=1, model=N) TP section instead of "
                         "the data-sharded one")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = (
            "BENCH_runtime_2d.json" if args.mesh2d
            else "BENCH_runtime_sharded.json"
        )
    if len(jax.devices()) < args.devices:
        # The argv peek above must have forced the host device count; a
        # 1-device run would make the twin parity check vacuous.
        raise SystemExit(
            f"need {args.devices} devices, have {len(jax.devices())} "
            "(invoke as `python -m benchmarks.runtime_bench --devices N`)"
        )
    if args.mesh2d:
        rows = runtime_2d(devices=args.devices, quick=args.quick)
    else:
        rows = runtime_sharded(devices=args.devices, quick=args.quick)
    for name, val in rows:
        print(f"{name},{val}")
    payload = {name: val for name, val in rows}
    with open(args.json, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {args.json}")

    def _one(suffix):
        return payload[[k for k in payload if k.endswith(suffix)][0]]

    if args.mesh2d:
        m = _one("model_parallel")
        if _one("serve_parity") != 1.0 or _one("pipe_parity") != 1.0:
            raise SystemExit("2-D/twin temp-0 token parity broken")
        if _one("backbone_bytes_ratio") < 0.8 * m:
            raise SystemExit(
                f"per-device backbone bytes ratio {_one('backbone_bytes_ratio'):.2f} "
                f"< 0.8*{m:.0f}"
            )
        if _one("pipe_wall_ratio") > _one("pipe_wall_bound"):
            raise SystemExit(
                f"pipelined admission wall ratio {_one('pipe_wall_ratio'):.2f} "
                f"exceeds the bubble-adjusted bound {_one('pipe_wall_bound'):.2f}"
            )
    else:
        parity = _one("twin_parity_max_abs_diff")
        if parity != 0.0:
            raise SystemExit(f"sharded/twin parity broken: {parity:.3e}")


if __name__ == "__main__":
    main()
