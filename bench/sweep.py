"""Find an open-loop cell's knee: run its mix at several fixed rates in one
process and print, per rate, whether the backlog grew.

    python bench/sweep.py --workload stablelm-chat --rates 1,2,3,4 \
        --seconds 90 [--seed 5]

A rate sustains when the scheduler's queue wait does not grow across the
window: the median wait of the requests due in its last third is within
``--grow`` seconds of the first third's, and every request due in the
window was admitted by the time it closed plus two seconds. The knee is
the highest such rate; a cell's mix then fixes its rate as a number.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--grow", type=float, default=0.5)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from bench import drive
    from bench.run import enable_cache, load_cell

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    enable_cache(ROOT)
    cell = load_cell(ROOT, args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell["mix"])
        mix["serve"]["rate_per_s"] = rate
        s = drive.Session(cell["config"], mix, args.seed, args.seconds)
        s.warm_up()
        s.run(args.seconds)
        t0, t1 = s.window
        due = [r for r in s.served if t0 <= r.due_at < t1]
        waits = np.array([
            (r.req.started_at if r.req.started_at is not None else np.inf)
            - r.due_at for r in due
        ])
        third = max(1, len(due) // 3)
        late = sum(1 for r in due if r.req.started_at is None
                   or r.req.started_at > t1 + 2.0)
        ttft = np.array([r.first_at - r.due_at for r in due if r.first_at])
        first, last = np.median(waits[:third]), np.median(waits[-third:])
        ok = bool(late == 0 and last - first <= args.grow)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "sustained": ok,
            "wait_first_third_s": float(first), "wait_last_third_s": float(last),
            "admitted_late": late,
            "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
            "ttft_p90_ms": float(np.percentile(ttft, 90) * 1e3),
            "ttft_p95_ms": float(np.percentile(ttft, 95) * 1e3),
            "tokens_per_s": s.tokens_in_window / (t1 - t0),
        }), flush=True)
        s.free_program()
        del s
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
