"""Lanes the serial tail gathers per adjacency entry, per request: the
``cells`` of the ``serial_tail`` span (lanes times widths over all its
rounds) over the graph's entries; the mean over the window's requests (0
without a tail).  None where a tail reports no ``cells``."""
from bench import program_spans as ps


def read(ctx):
    requests = ps.window_requests(ctx)
    if requests is None:
        return None
    tails = [e for _, desc in requests for e in desc if e.name == "serial_tail"]
    if any("cells" not in e.meta for e in tails):
        return None
    return ps.mean_per_request(requests, lambda root, desc: sum(
        e.meta["cells"] for e in desc if e.name == "serial_tail")) / max(
            ctx["graph"].entries, 1)
