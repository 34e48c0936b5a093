"""Vertices the serial tail colors one at a time, per request: the
``vertices`` of the ``serial_tail`` span; the mean over the window's
requests (0 without a tail)."""
from bench import program_spans as ps


def read(ctx):
    requests = ps.window_requests(ctx)
    if requests is None:
        return None
    return ps.mean_per_request(requests, lambda root, desc: sum(
        e.meta.get("vertices", 0) for e in desc if e.name == "serial_tail"))
