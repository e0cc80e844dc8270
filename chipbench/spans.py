"""Reduction of the program's own host spans (``presto.*``) in a trace.

The program opens its spans itself (``repro.common.trace``); each metric file
names the spans it reads.  A span counts when it starts inside the traced
session's window.  Where a trace holds none of the spans named, as one of
a program without them does, every reader returns None.
"""

from __future__ import annotations


def started_in_window(trace, names) -> list:
    """(line, start_ns, end_ns) of the host spans called one of `names` that
    start inside the window."""
    w = trace.window()
    if w is None:
        return []
    lo, hi = w
    return [(ln, s, e) for ln, n, s, e in trace.host if n in names and lo <= s < hi]


def per_partition_ms(ctx, names):
    """Summed duration of the spans called one of `names`, in ms per
    partition of the traced session; None where there are none."""
    spans = started_in_window(ctx.trace, names)
    if not spans or ctx.partitions <= 0:
        return None
    return sum(e - s for _, s, e in spans) / ctx.partitions / 1e6


def busy_share(trace, worker_span: str, idle_span: str):
    """Mean over worker threads (lines holding a `worker_span`) of 1 less the
    share of the window their `idle_span` spans cover, in %; None where no
    line holds a `worker_span`."""
    w = trace.window()
    workers = {ln for ln, n, _s, _e in trace.host if n == worker_span}
    if w is None or not workers or w[1] <= w[0]:
        return None
    lo, hi = w
    idle = dict.fromkeys(workers, 0)
    for ln, n, s, e in trace.host:
        if n == idle_span and ln in idle and e > lo and s < hi:
            idle[ln] += min(e, hi) - max(s, lo)
    return 100.0 * sum(1 - t / (hi - lo) for t in idle.values()) / len(idle)
