"""End-to-end metrics, each a function of a finished window (``Run``).
All are host-clock readings; a metric a cell's traffic cannot give
returns None."""

from __future__ import annotations

import functools
import re

import numpy as np


def _due(run) -> list:
    """Requests whose latency counts: all due in the window (open loop),
    or those submitted in it that finished (closed loop)."""
    s = run.session
    if s.stream is None:
        return []
    t0, t1 = s.window
    if s.stream.open:
        return [r for r in s.served if t0 <= r.due_at < t1]
    return [r for r in s.served if r.done]


def ttft(run, q: float):
    """The ``q``-th percentile over requests due in the window of (host
    sees the first token - the request's due time). A request that never
    produced a token counts as infinitely late."""
    reqs = _due(run)
    if not reqs or not run.session.stream.open:
        return None
    v = [(r.first_at - r.due_at) if r.first_at is not None else np.inf for r in reqs]
    return float(np.percentile(v, q) * 1e3)


def tpot(run, q: float):
    """The ``q``-th percentile over those requests of (last token seen -
    first token seen) / (tokens - 1)."""
    reqs = _due(run)
    if not reqs or not run.session.stream.open:
        return None
    v = [
        (r.last_at - r.first_at) / (r.planned.max_new - 1)
        if r.last_at is not None and r.first_at is not None else np.inf
        for r in reqs if r.planned.max_new > 1
    ]
    return float(np.percentile(v, q) * 1e3)


def tokens_per_s(run):
    """Output tokens delivered to the host inside the window, over it."""
    s = run.session
    if s.stream is None:
        return None
    t0, t1 = s.window
    return s.tokens_in_window / (t1 - t0)


def finetune_samples_per_s(run):
    """Sample-epochs of fine-tune jobs completed in the window, over the
    time from the window's start to the end of the last completed job."""
    s = run.session
    if s.ft is None or not s.jobs_done:
        return None
    ft = s.ft
    per = int(ft["samples"]) * int(ft["epochs"])
    n = sum(len(c["jobs"]) for c in s.jobs_done) * per
    return n / (s.jobs_done[-1]["end"] - s.window[0])


def setup_s(run):
    """Process start to window start: imports, weights, compile, warm-up."""
    return run.setup_s


METRICS = {
    "tokens_per_s": tokens_per_s,
    "finetune_samples_per_s": finetune_samples_per_s,
    "setup_s": setup_s,
}


def metric(name: str):
    """The function behind an end-to-end metric's name; ``ttft_pNN_ms``
    and ``tpot_pNN_ms`` name their percentile."""
    m = re.fullmatch(r"(ttft|tpot)_p(\d+)_ms", name)
    if m:
        return functools.partial(ttft if m[1] == "ttft" else tpot, q=float(m[2]))
    return METRICS[name]


def summary(run) -> dict:
    """What a serve window held, for the record beside the metrics:
    request counts, latency percentiles, the mean live depth of the rows
    that got tokens against the cache's depth, and how late the generator
    submitted (submit time - due time)."""
    reqs = _due(run)
    out = {"requests": len(reqs),
           "finished": sum(1 for r in reqs if r.done)}
    if run.session.stream is None or not reqs:
        return out
    if run.session.stream.open:
        for q in (50, 75, 90, 95):
            out[f"ttft_p{q}_ms"] = ttft(run, q)
            out[f"tpot_p{q}_ms"] = tpot(run, q)
    log = run.session.step_log
    rows = sum(st["rows"] for st in log)
    if rows:
        kw = run.session.sched_kw
        out["live_depth_mean"] = sum(st["depth"] for st in log) / rows
        out["max_seq"] = kw["max_prompt"] + kw["max_new_cap"]
    late = np.array([r.submitted_at - r.due_at for r in reqs])
    out["generator_late_p95_ms"] = float(np.percentile(late, 95) * 1e3)
    out["generator_late_max_ms"] = float(late.max() * 1e3)
    return out
