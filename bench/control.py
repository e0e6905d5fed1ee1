"""Readings that set a cell's limits: the program on many seeds (the lower
reading) and the control, the reference computed in float8 put in the
program's place, on a few (the upper reading), at the cell's own size, in
one process.

    python bench/control.py --workload stablelm-chat --seeds 101-112 \
        --control-seeds 101-103 --seconds 12 [--fault slot]

Serve cells run a short window at the cell's own load per seed and compare
the finished sample, as a run does; the control reads, at every position
of the same prompts and served tokens, the reference gap of the token the
float8 reference ranks first. ``--fault slot`` plants a wrong adapter
slot in the program: every adapted tenant's rows read the pool's zero
slot (the base model), and the readings show whether ``serve_gap``
catches it. Fine-tune cells need no window: set-up runs
the check cohort, and the control and the half-batch fault are reference
runs put in the program's place. Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def plant_zero_slot() -> None:
    """The wrong-slot fault: the pool resolves every tenant to its zero
    slot, so adapted tenants are served the base model."""
    import jax.numpy as jnp

    from repro.core import adapter_pool as AP

    AP.ShardedAdapterPool.lookup_local = (
        lambda self, s, tenants: jnp.full((len(tenants),), AP.ZERO_SLOT, jnp.int32))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--fault", choices=("slot",), default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import check as C
    from bench import drive
    from bench.run import enable_cache, load_cell

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    enable_cache(ROOT)
    cell = load_cell(ROOT, args.workload)
    if args.fault == "slot":
        plant_zero_slot()
    ctrl = set(seeds(args.control_seeds)) if args.control_seeds else set()
    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        s = drive.Session(cell["config"], cell["mix"], seed, args.seconds)
        s.warm_up()
        out = {"seed": seed, "fault": args.fault}
        if s.stream is not None:
            s.run(args.seconds)
            out["attempted"], out["failed"] = C.whole(s)
            sample = C.sample_served(s, int(cell["limits"]["sample_tokens"]))
            params = s.free_program()
            gc.collect()
            sc = s.sched_kw
            out.update(C.serve_readings(params, s, sample,
                                        sc["max_prompt"] + sc["max_new_cap"],
                                        control=seed in ctrl))
        if s.ft is not None:
            params = s.free_program()
            gc.collect()
            ref = C.job_reference(params, s)
            out.update(C.job_readings(s, ref))
            if seed in ctrl:
                fp8 = C.job_reference(params, s, precision="fp8")
                out["control"] = C.job_readings(s, ref, prog=fp8)
                del fp8
                half = C.job_reference(params, s, keep_rows=0.5)
                out["half_batch"] = C.job_readings(s, ref, prog=half)
                del half
            del ref
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        del s, params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
