"""The plain reference: what a proper coloring is, checked edge by edge.

Independent of the program: it imports nothing from ``repro`` and takes
nothing the program made except the colors under test.

* ``improper_vertices`` is the check that decides ``correct``: the
  vertices left uncolored (color < 1) or joined by an edge to a vertex of
  the same color.  A proper coloring reads 0.
* ``greedy_coloring`` is the serial greedy of the paper's Algorithm 1
  (first fit, in the order given), the baseline its speed is set against.
"""
from __future__ import annotations

import numpy as np


def improper_vertices(n: int, src, dst, colors) -> int:
    """Vertices of an ``n``-vertex graph with edges ``(src, dst)`` (each
    undirected edge at least once) that break a proper coloring: colorless
    (color < 1) or sharing their color with a neighbor.  A ``colors`` array
    that is not one color per vertex leaves every vertex improper."""
    colors = np.asarray(colors)
    if colors.shape != (n,):
        return n
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    bad = colors < 1
    same = colors[src] == colors[dst]
    bad[src[same]] = True
    bad[dst[same]] = True
    return int(np.count_nonzero(bad))


def greedy_coloring(row_offsets, col_indices, order=None) -> np.ndarray:
    """Serial first-fit: each vertex in ``order`` (default: by id) takes the
    smallest color >= 1 that no already-colored neighbor holds."""
    n = len(row_offsets) - 1
    colors = np.zeros(n, dtype=np.int64)
    stamp = np.full(n + 2, -1, dtype=np.int64)  # stamp[c] == v: c is taken
    for v in (range(n) if order is None else order):
        nbr = colors[col_indices[row_offsets[v]:row_offsets[v + 1]]]
        stamp[nbr[nbr <= n]] = v
        c = 1
        while stamp[c] == v:
            c += 1
        colors[v] = c
    return colors
