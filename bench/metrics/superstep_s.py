"""Super-step time per request (s): the summed ``superstep`` spans, each
ending at the count reads that wait for its step on the device; the mean
over the window's requests."""
from bench import program_spans as ps


def read(ctx):
    requests = ps.window_requests(ctx)
    if requests is None:
        return None
    return ps.mean_per_request(
        requests, lambda root, desc: ps.seconds(desc, "superstep"))
