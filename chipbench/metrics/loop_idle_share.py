"""Pump loop: share of the traced span in which the device was idle while
the pump thread was inside one of its host phases (a ``serve.*`` span
other than the two waits on the device, ``serve.decode_wait`` and
``serve.first_token_wait``), in %, averaged over chips."""
import bisect
import itertools

from trace_reduce import gaps, union

PREFIX = "serve."
WAITS = ("serve.decode_wait", "serve.first_token_wait")


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    host = run.trace.host
    spans = union([(s, e) for s, e, n in host
                   if n.startswith(PREFIX) and n not in WAITS])
    if not spans:
        return None
    waits = union([(s, e) for s, e, n in host if n in WAITS])
    phases = [g for s, e in spans for g in gaps(waits, s, e)]
    idle = 0
    for d in run.trace.devices:
        busy = d["busy"]
        ends = [e for _, e in busy]
        for s, e in phases:
            i = bisect.bisect_right(ends, s)
            idle += sum(b - a for a, b in
                        gaps(itertools.islice(busy, i, None), s, e))
    n = max(len(run.trace.devices), 1)
    return 100.0 * idle / n / (run.trace.window_s * 1e9)
