"""Peaks by device and the least traffic a coloring needs.

``PEAKS`` is ``peaks.json``, keyed by JAX's ``device_kind``; a device that
is not in the table is an error, never a default.

``coloring_min_bytes`` is the least HBM traffic of any greedy coloring of
a CSR graph, whoever implements it: the row offsets once (4 (n + 1) B),
every adjacency entry and its neighbor's color once (8 B per entry), and
one color write per vertex (4 B).  The work is integer compares, a few per
entry, so HBM bandwidth bounds it and the FLOP peak plays no part.  As a
lower bound on the traffic, its share of the bandwidth bound cannot pass
100% unless the device time is measured short.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = json.loads((Path(__file__).resolve().parent / "peaks.json").read_text())


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(PEAKS)})") from None


def coloring_min_bytes(n: int, entries: int) -> int:
    return 4 * (n + 1) + 8 * entries + 4 * n


def roofline_share(bytes_moved: float, busy_s: float, device_kind: str) -> float:
    """Percent of the HBM roofline: the time the bytes need at peak over
    the device time they took."""
    if busy_s <= 0:
        raise ValueError("no device time to set the bytes against")
    return 100.0 * bytes_moved / (peak(device_kind)["hbm_bytes_per_s"] * busy_s)
