"""Runtime adapt (``SessionRuntime.adapt``): host share of the window, in
%: over the program's ``adapt`` spans that start in the window, the sum
of (first ``adapt.launch`` start - the span's start: fresh adapters,
grouping, stacking and ``device_put``, the skip cache's export, the first
batch plan) and (the span's end - its ``adapt.sync`` end), over the
window: the host work before the call's first launch and after its
losses come back, which no device work of the call overlaps. Moves
``finetune_samples_per_s``. Reads nothing where the program has no span
ring, or the ring dropped spans of the window."""


def read(run):
    s = run.session
    ring = getattr(s.rt, "spans", None)
    if ring is None or s.ft is None:
        return None
    t0, t1 = s.window
    if ring.dropped and ring.lost_until >= t0:
        return None
    recs = ring.records()
    calls = {r.id: r for r in recs if r.name == "adapt" and t0 <= r.start < t1}
    first, synced = {}, {}
    for r in recs:
        if r.parent not in calls:
            continue
        if r.name == "adapt.launch":
            first[r.parent] = min(first.get(r.parent, r.start), r.start)
        elif r.name == "adapt.sync":
            synced[r.parent] = r.end
    host = sum(first[i] - a.start + a.end - synced[i]
               for i, a in calls.items() if i in first and i in synced)
    return 100.0 * host / (t1 - t0) if first else None
