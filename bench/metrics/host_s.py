"""Host time per request (s): the ``color`` root span less its
``superstep`` and ``serial_tail`` spans, that is class planning, CSR
build and upload enqueue, worklist slicing and result assembly; the mean
over the window's requests."""
from bench import program_spans as ps


def read(ctx):
    requests = ps.window_requests(ctx)
    if requests is None:
        return None
    return ps.mean_per_request(requests, ps.host_self_s)
