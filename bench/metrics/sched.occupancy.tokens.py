"""Request scheduler: mean over the window's ``scheduler.step()`` calls of
the rows that received tokens, over ``max_batch``, in %. Moves
``tokens_per_s``."""


def read(run):
    s = run.session
    if not s.step_log:
        return None
    rows = [st["rows"] for st in s.step_log]
    return 100.0 * sum(rows) / len(rows) / s.sched_kw["max_batch"]
