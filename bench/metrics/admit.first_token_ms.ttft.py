"""Admission (``scheduler._dispatch`` and ``_harvest``): median over the
requests due in the window of (the program's ``Request.first_token_at``,
when the host holds the first token, - ``Request.started_at``, when the
request was admitted), in ms: the admission dispatch's prefill and chunk
and the wait for them, without the queue. Moves ``ttft_p50_ms``. A
program without the stamp reads nothing."""

import numpy as np


def read(run):
    s = run.session
    if s.stream is None or not s.stream.open:
        return None
    t0, t1 = s.window
    gaps = [
        r.req.first_token_at - r.req.started_at
        for r in s.served
        if t0 <= r.due_at < t1 and r.req.started_at is not None
        and getattr(r.req, "first_token_at", None) is not None
    ]
    return float(np.percentile(gaps, 50) * 1e3) if gaps else None
