"""Request scheduler (``RequestScheduler.step``): host share of the
window, in %: over the program's ``sched.step`` spans that start in the
window, the sum of (span length - its ``sched.wait`` children, where the
host waits on the device), over the window: the host's part of the tick
(plan, launch, harvest, ingest), which the synchronous tick does not
overlap with the device's work. Moves ``tpot_p50_ms``. Reads nothing where the program has
no span ring, or the ring dropped spans of the window."""


def read(run):
    s = run.session
    ring = getattr(s.rt, "spans", None)
    if ring is None or s.sched is None:
        return None
    t0, t1 = s.window
    if ring.dropped and ring.lost_until >= t0:
        return None
    recs = ring.records()
    ticks = {r.id: r for r in recs if r.name == "sched.step" and t0 <= r.start < t1}
    if not ticks:
        return None
    host = sum(r.end - r.start for r in ticks.values())
    host -= sum(r.end - r.start for r in recs
                if r.name == "sched.wait" and r.parent in ticks)
    return 100.0 * host / (t1 - t0)
