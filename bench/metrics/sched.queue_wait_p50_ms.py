"""Request scheduler (``core/scheduler.py``): median over the
requests due in the window of (admission, the program's
``Request.started_at`` - the request's due time), in ms. Moves
``ttft_p50_ms``."""

import numpy as np


def read(run):
    s = run.session
    if s.stream is None or not s.stream.open:
        return None
    t0, t1 = s.window
    waits = [
        r.req.started_at - r.due_at
        for r in s.served
        if t0 <= r.due_at < t1 and r.req.started_at is not None
    ]
    return float(np.percentile(waits, 50) * 1e3) if waits else None
