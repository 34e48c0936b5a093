"""R-MAT generator (Chakrabarti et al., SDM'04), as the paper's §4 uses it.

A copy of the program's ``repro.graphs.rmat.rmat``: one quadrant draw per
recursion level for every edge at once, endpoints past ``n`` dropped, then
the clean undirected CSR.  Every draw comes from the run's seed.

Parameters: ``n`` vertices, ``avg_degree`` (``n * avg_degree / 2`` edge
draws) and ``abcd``, the four quadrant probabilities.
"""
from __future__ import annotations

import numpy as np

from bench.graphs.csr import Graph, csr_from_edges


def generate(params: dict, rng: np.random.Generator) -> Graph:
    n = int(params["n"])
    a, b, c, d = (float(x) for x in params["abcd"])
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"R-MAT probabilities sum to {a + b + c + d}, not 1")
    levels = int(np.ceil(np.log2(max(n, 2))))
    m = int(n * float(params["avg_degree"]) / 2)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for lvl in range(levels):
        u = rng.random(m)
        bottom = u >= (a + b)
        right = ((u >= a) & ~bottom) | (u >= (a + b + c))
        bit = 1 << (levels - 1 - lvl)
        src += bottom * bit
        dst += right * bit
    keep = (src < n) & (dst < n)
    return csr_from_edges(n, src[keep], dst[keep])
