"""Lanes the super-step gathers per adjacency entry: each ``superstep``
span's ``cells`` (the lanes times tile widths its degree classes
dispatched) over the graph's entries, the mean over the window's
super-steps.  None where the program's super-steps carry no ``cells``."""
from bench import program_spans as ps


def read(ctx):
    requests = ps.window_requests(ctx)
    if requests is None:
        return None
    cells = [e.meta.get("cells") for _, desc in requests for e in desc
             if e.name == "superstep"]
    if not cells or None in cells:
        return None
    return sum(cells) / len(cells) / max(ctx["graph"].entries, 1)
