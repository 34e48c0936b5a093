"""The benchmark's own CSR container and constructor (no import of the program).

A graph is the pair of arrays the program is handed: ``row_offsets``
(n + 1, int64) and ``col_indices`` (entries, int32), undirected, without
self-loops or duplicate entries, each row sorted.  ``csr_from_edges``
builds exactly the arrays that ``repro.core.csr.csr_from_edges`` builds
from the same edge list, so a generator copied from the program gives the
program's graph.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    n: int
    row_offsets: np.ndarray  # (n + 1,) int64
    col_indices: np.ndarray  # (entries,) int32

    @property
    def entries(self) -> int:
        """Directed adjacency entries: twice the undirected edge count."""
        return int(self.col_indices.shape[0])

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.row_offsets)

    def undirected_edges(self) -> tuple[np.ndarray, np.ndarray]:
        """Each edge once as ``(src, dst)`` with ``src < dst``, in key order."""
        src = np.repeat(np.arange(self.n, dtype=np.int64), self.degrees)
        dst = self.col_indices.astype(np.int64)
        keep = src < dst
        return src[keep], dst[keep]


def csr_from_edges(n: int, src, dst) -> Graph:
    """Undirected CSR from an edge list: self-loops dropped, edges
    symmetrized and deduplicated, rows sorted."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    row_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=row_offsets[1:])
    return Graph(n, row_offsets, (key % n).astype(np.int32))


def relabel(g: Graph, perm: np.ndarray) -> Graph:
    """The same graph with vertex ``v`` renamed ``perm[v]``."""
    perm = np.asarray(perm, dtype=np.int64)
    src = np.repeat(perm, g.degrees)
    key = np.sort(src * g.n + perm[g.col_indices])
    row_offsets = np.zeros(g.n + 1, dtype=np.int64)
    np.cumsum(np.bincount(key // g.n, minlength=g.n), out=row_offsets[1:])
    return Graph(g.n, row_offsets, (key % g.n).astype(np.int32))
