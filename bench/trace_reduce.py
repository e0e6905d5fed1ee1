"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

  busy      union of the intervals in which an operation ran on a device
            (the ``XLA Ops`` line of each ``/device:`` plane), inside the
            window, averaged over the devices
  ops       device self seconds per operation inside the window: an
            event's time less that of the events nested in it (a while
            loop's body ops nest in the loop's event)
  gaps      each interval of the window with no device operation, labelled
            by the innermost harness host span (``bench.*``) that covers
            its midpoint, or ``none``

The window is the trace's ``bench.window`` host span. Host and device
events share the profiler's clock.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """An op's own name from its trace text (``%copy.58 = bf16[...] ...``
    -> ``copy.58``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(events) -> list[tuple[str, float, float, float]]:
    """(name, start, end, self ns) per event: duration less the time of
    the events directly nested in it."""
    out = []
    stack: list[list] = []          # [name, start, end, child ns]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            n, sa, sb, c = stack.pop()
            out.append((n, sa, sb, (sb - sa) - c))
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    for n, sa, sb, c in reversed(stack):
        out.append((n, sa, sb, (sb - sa) - c))
    return out


def read_events(path: str):
    """(device ops {plane: [(name, start_ns, end_ns)]}, host spans
    [(name, start_ns, end_ns)])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name[len(SPAN_PREFIX):], e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                )
    return ops, spans


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``intervals`` clipped to [lo, hi]."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def label_gap(spans, starts, mid: float) -> str:
    """The harness host span covering ``mid`` (spans sorted by start; the
    harness's spans do not nest), or none."""
    i = bisect.bisect_right(starts, mid) - 1
    if i >= 0 and spans[i][2] >= mid:
        return spans[i][0]
    return "none"


def reduce(ops: dict, spans: list, lo: float, hi: float, top: int = 10) -> dict:
    """Reduce events to the window [lo, hi] (ns). Returns busy_s,
    window_s, ``op_s`` (name -> device seconds), and the ``top`` device
    operations and idle gaps (by host span) by time."""
    n_dev = max(1, len(ops))
    window = WINDOW_SPAN[len(SPAN_PREFIX):]
    spans = sorted((s for s in spans if s[0] != window), key=lambda s: s[1])
    starts = [s[1] for s in spans]
    busy = 0.0
    op_s: dict[str, float] = defaultdict(float)
    gaps: dict[str, float] = defaultdict(float)
    for events in ops.values():
        for name, a, b, own in self_times(events):
            if b > a and lo <= a and b <= hi:
                op_s[name] += own / 1e9 / n_dev
            elif min(b, hi) > max(a, lo):
                # cut by the window's edge: its share of the overlap
                op_s[name] += own * (min(b, hi) - max(a, lo)) / (b - a) / 1e9 / n_dev
        merged = merge(((a, b) for _, a, b in events), lo, hi)
        busy += sum(b - a for a, b in merged) / 1e9 / n_dev
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[label_gap(spans, starts, (a + b) / 2)] += (b - a) / 1e9 / n_dev
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy,
        "window_s": (hi - lo) / 1e9,
        "op_s": dict(op_s),
        "device_ops": [[k, v] for k, v in by_time(op_s)],
        "idle_gaps": [[k, v] for k, v in by_time(gaps)],
    }


def reduce_trace(trace_dir: str) -> dict:
    """Read the newest trace under ``trace_dir`` and reduce it to its
    ``bench.window`` span."""
    ops, spans = read_events(find_xplane(trace_dir))
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN[len(SPAN_PREFIX):]]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    lo, hi = windows[0]
    return reduce(ops, spans, lo, hi)


def kernel_seconds(op_s: dict, prefix: str) -> float:
    """Device seconds of every operation whose name, less its ``.N``
    instance suffix, starts with ``prefix`` (``skip_lora_grouped_fwd``
    matches ``skip_lora_grouped_fwd.14``)."""
    return sum(v for k, v in op_s.items() if k.startswith(prefix))
