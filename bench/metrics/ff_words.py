"""The most bitset words a FirstFit of the window dispatched: the largest
``ff_words`` of the window's ``superstep`` and ``serial_tail`` spans.
None where no span carries it."""
from bench import program_spans as ps


def read(ctx):
    requests = ps.window_requests(ctx)
    if requests is None:
        return None
    words = [e.meta["ff_words"] for _, desc in requests for e in desc
             if e.name in ("superstep", "serial_tail")
             and "ff_words" in e.meta]
    return max(words) if words else None
