"""Chip benchmark of the multi-tenant skip-LoRA service.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip it is started on: builds
the session the cell's configuration (``bench/configs/<config>.json``) and
traffic mix (``bench/traffic/<traffic>.json``, whose parts name their
drivers, ``bench/traffic/<driver>.py``) describe, warms up every
program the window runs, measures for ``--seconds``, then checks what the
timed path produced against the plain reference (``bench/check.py``,
limits in ``bench/limits/<cell>.json``). With ``--trace 0`` the result
line holds the cell's end-to-end metrics; with ``--trace 1`` the window
runs under the profiler and the line holds its per-layer metrics (those
whose ``workloads`` list the cell), each read by
``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object. A run that finds no
accelerator, or fewer chips than the cell asks for, exits 1 and prints
none.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_cell(root: str, workload: str) -> dict:
    """Everything that belongs to one cell, found by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in spec["configs"] if c["name"] == entry["config"])

    def read(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)

    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    per_layer = [m for m in spec["per_layer"] if workload in m["workloads"]]
    return {
        "root": root,
        "workload": entry,
        "config": read(config["file"]),
        "mix": read("bench", "traffic", f"{entry['traffic']}.json"),
        "limits": read("bench", "limits", f"{workload}.json"),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def metric_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_cache(root: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program (no size or compile-time floor)."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".bench_cache", "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Run:
    """A finished window as the metric readers see it."""

    def __init__(self, cell, session, setup_s, trace, device_kind):
        self.cell, self.session, self.setup_s = cell, session, setup_s
        self.trace = trace                  # trace_reduce.reduce(...) or None
        self.device_kind = device_kind
        self.cfg = session.cfg


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             t0: float) -> tuple[dict, list[str]]:
    """Set up, measure, check. Returns (result line, check lines)."""
    import jax

    from bench import check as C
    from bench import drive
    from bench import e2e as E
    from bench import trace_reduce as TR

    dev = jax.devices()[0]
    session = drive.Session(cell["config"], cell["mix"], seed, seconds,
                            root=cell["root"])
    session.warm_up()
    setup_s = time.perf_counter() - t0

    trace_dir = os.path.join(cell["root"], ".bench_cache", "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        session.spans.annotate = True
        jax.profiler.start_trace(trace_dir)
    try:
        session.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    reduced = None
    if trace:
        reduced = TR.reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = Run(cell, session, setup_s, reduced, dev.device_kind)
    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            v = metric_reader(cell["root"], m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": E.metric(m["name"])(run),
                                  "unit": m["unit"]}
    window = E.summary(run) if session.stream is not None else {}
    stats = dev.memory_stats() or {}
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    # Correctness, once the program's state is freed.
    limits = cell["limits"]["limits"]
    readings: dict = {}
    attempted = failed = 0
    if session.stream is not None:
        attempted, failed = C.whole(session)
        sample = C.sample_served(session, int(cell["limits"]["sample_tokens"]))
    if session.ft is not None:
        attempted += len(session.jobs_done) * int(session.ft["cohort"])
    params = session.free_program()
    gc.collect()
    if session.stream is not None:
        if not sample:
            readings["serve_gap"] = float("inf")
        else:
            readings.update(C.serve_readings(
                params, session, sample, session.sched_kw["max_prompt"]
                + session.sched_kw["max_new_cap"]))
    if session.ft is not None:
        readings.update(C.job_readings(session, C.job_reference(params, session)))
    checks = {k: {"value": readings[k], "limit": v} for k, v in limits.items()}
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    lines = ["window " + json.dumps(window)] if window else []
    lines += [f"check {k}: {c['value']!r} limit {c['limit']!r}"
              for k, c in checks.items()]
    lines.append(f"check failed_requests: {failed} limit 0")
    checks["failed"] = {"value": failed, "limit": 0}
    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    extra = {k: v for k, v in readings.items() if k not in checks}
    if extra:
        lines.insert(0, "readings " + json.dumps(extra))
    result["checks"] = checks
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    cell = load_cell(ROOT, args.workload)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
              "the benchmark runs on the chip only", file=sys.stderr)
        return 1
    chips = int(cell["workload"]["chips"])
    if len(devices) < chips:
        print(f"bench: the cell asks for {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    enable_cache(ROOT)
    result, lines = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             t0=T0)
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
