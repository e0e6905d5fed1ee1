"""Admission (``scheduler._dispatch``, dense ``sched_admit``): share of the
admission prefill's token slots that hold padding,
1 - real prompt tokens / (admission dispatches x ``admit_bucket`` x
``max_prompt``), over the window, in %. Dispatch counts are the
scheduler's counters; prompt lengths are the harness's own. Moves
``ttft_p50_ms``."""


def read(run):
    s = run.session
    if s.sched is None and not s.step_log:
        return None
    dispatches = sum(st["admits"] for st in s.step_log)
    if not dispatches:
        return None
    t0, t1 = s.window
    real = sum(
        r.planned.prompt.size for r in s.served
        if r.req.started_at is not None and t0 <= r.req.started_at < t1
    )
    kw = s.sched_kw
    slots = dispatches * kw["admit_bucket"] * kw["max_prompt"]
    return 100.0 * (1.0 - real / slots)
