"""The program's own phase spans (``repro.obs``), for the per-layer readers.

``repro.color`` opens a root span ``color`` around each request; under it
the engine's ``superstep`` spans each end at the count reads of their step,
and its ``serial_tail`` span ends when the device is done with the tail.
Both are stamped with ``time.time_ns()``.

``window_requests`` returns the window's requests, the last
``ctx["layer"]["requests"]`` ``color`` roots with their descendants, or
None where the program keeps no such spans.  The rest are pure functions
over ``(root, descendants)`` pairs, tested on synthetic spans.
"""
from __future__ import annotations

import statistics

from bench import profile_reduce as pr

ROOT = "color"
# spans inside which the host waits for the device
DEVICE_WAITS = ("superstep", "serial_tail")
# the annotation the benchmark opens around each ``repro.color`` request
REQUEST_ANNOTATION = "bench.color"


def window_requests(ctx):
    """The window's ``(root, descendants)`` pairs, oldest first, or None."""
    k = ctx["layer"].get("requests")
    if not k:
        return None
    try:
        from repro.obs import last_requests
    except ImportError:  # a program without the span ring
        return None
    return last_requests(k, root=ROOT) or None


def seconds(spans, name: str) -> float:
    """Summed duration of the spans named ``name`` (seconds)."""
    return sum(e.duration for e in spans if e.name == name)


def mean_per_request(requests, value) -> float:
    """Mean over the requests of ``value(root, descendants)``."""
    return sum(value(root, desc) for root, desc in requests) / len(requests)


def host_self_s(root, desc) -> float:
    """The root's duration less its device waits: host work of the request."""
    return root.duration - sum(seconds(desc, n) for n in DEVICE_WAITS)


def clock_offset_ns(requests, annotations, lo: float, hi: float):
    """Span clock minus trace clock (ns), or None.

    The profiler's trace counts from its own start; the spans count from
    the epoch.  Each request's root span closes just before the benchmark's
    ``bench.color`` annotation around it does (a few microseconds), so the
    median of the differences of their ends, request by request, is the
    offset."""
    ends = sorted(e for s, e, name in annotations
                  if name == REQUEST_ANNOTATION and lo <= e <= hi)
    roots = [root for root, _ in requests]
    if not ends or len(ends) < len(roots):
        return None
    ends = ends[-len(roots):]
    return statistics.median_low(r.end_ns - int(e)
                                 for r, e in zip(roots, ends))


def idle_outside_ns(programs, waits, lo: float, hi: float) -> float:
    """Device idle time in ``[lo, hi]`` that no wait interval covers."""
    idle = 0.0
    covered = pr.merge(waits)
    for gs, ge in pr.idle_gaps(programs, lo, hi):
        idle += ge - gs
        for ws, we in covered:
            idle -= max(0.0, min(ge, we) - max(gs, ws))
    return idle
