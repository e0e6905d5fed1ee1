"""Device: 1 - (union of device operation intervals) / (traced window),
in %, in a cell that reports ``tokens_per_s``. Moves ``tokens_per_s``."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
