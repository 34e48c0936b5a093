"""Serial-tail time per request (s): the duration of the ``serial_tail``
span, which holds the tail's ordering and loop and ends when the device
is done with them; the mean over the window's requests (0 without a
tail)."""
from bench import program_spans as ps


def read(ctx):
    requests = ps.window_requests(ctx)
    if requests is None:
        return None
    return ps.mean_per_request(
        requests, lambda root, desc: ps.seconds(desc, "serial_tail"))
