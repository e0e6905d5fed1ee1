"""Model step (``models/lm.decode_step`` via the scheduler's chunk scan):
median host time of a ``scheduler.step()`` in the window that admitted
nothing, divided by its ``chunk`` decode steps, in ms. Moves
``tpot_p50_ms``."""

import numpy as np


def read(run):
    s = run.session
    times = [st["s"] for st in s.step_log if st["admits"] == 0]
    if not times:
        return None
    return float(np.median(times) / s.sched_kw["chunk"] * 1e3)
