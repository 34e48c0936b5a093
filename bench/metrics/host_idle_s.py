"""Device idle time the host causes, per request (s): the idle stretches
of the traced window (no ``XLA Modules`` program running) outside every
``superstep`` and ``serial_tail`` span, whose device is busy by
construction; the sum over the window, over its requests.  The spans are
put on the trace's clock by ``program_spans.clock_offset_ns``."""
from bench import program_spans as ps


def read(ctx):
    summary = ctx.get("trace")
    requests = ps.window_requests(ctx)
    if summary is None or requests is None or not summary.devices:
        return None
    lo, hi = ctx["window_ns"]
    offset = ps.clock_offset_ns(requests, summary.annotations, lo, hi)
    if offset is None:
        return None
    waits = [(e.start_ns - offset, e.end_ns - offset)
             for _, desc in requests for e in desc
             if e.name in ps.DEVICE_WAITS]
    idle = sum(ps.idle_outside_ns(d.programs, waits, lo, hi)
               for d in summary.devices) / len(summary.devices)
    return idle * 1e-9 / len(requests)
