"""Dependency rounds of the serial tail per request: the ``rounds`` of the
``serial_tail`` span, the longest chain of tail vertices each of which
precedes the next in the tail's order; the mean over the window's
requests (0 without a tail).  None where the tail reports no rounds (a
program that colors it one vertex at a time)."""
from bench import program_spans as ps


def read(ctx):
    requests = ps.window_requests(ctx)
    if requests is None:
        return None
    tails = [e for _, desc in requests for e in desc if e.name == "serial_tail"]
    if any("rounds" not in e.meta for e in tails):
        return None
    return ps.mean_per_request(requests, lambda root, desc: sum(
        e.meta["rounds"] for e in desc if e.name == "serial_tail"))
