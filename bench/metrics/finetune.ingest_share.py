"""Runtime ingest and skip-cache engine (``SessionRuntime.ingest``,
``core/cache_engine``): share of the window's wall time spent inside
``ingest`` calls, each ending once its logits are ready, in %. Moves
``finetune_samples_per_s``."""


def read(run):
    s = run.session
    if s.ft is None:
        return None
    t0, t1 = s.window
    inside = sum(min(b, t1) - a for n, a, b in s.spans.spans
                 if n == "ingest" and t0 <= a < t1)
    return 100.0 * inside / (t1 - t0)
