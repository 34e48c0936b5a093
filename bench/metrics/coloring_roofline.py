"""The traced colorings' share of the HBM roofline (%): the least traffic
any greedy coloring of the graph needs (``roofline.coloring_min_bytes``),
times the colorings in the window, at the device's peak bandwidth
(``peaks.json``), over the device busy time they took."""
from bench import roofline


def read(ctx):
    requests = ctx["layer"].get("requests")
    if not ctx.get("busy_s") or not requests:
        return None
    g = ctx["graph"]
    return roofline.roofline_share(
        requests * roofline.coloring_min_bytes(g.n, g.entries),
        ctx["busy_s"], ctx["device"]["kind"])
