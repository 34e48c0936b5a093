"""Streaming churn benchmark (§14): incremental recolor vs cold re-color.

Measures the dynamic engine's claim — ``session.recolor()`` after a small
edge delta does frontier-proportional work — against the cold fused engine
rerun from scratch on the mutated graph.  Deltas are deterministic
(seeded): each round deletes ``churn`` of the undirected edges and inserts
the same number of fresh random pairs, the classic sliding-window stream.

CSV rows (per suite graph): incremental/cold wall-clock per round and the
work ratio.  ``bench_dynamic_json`` writes the machine-readable churn
records consumed by ``run.py --engine dynamic`` and the CI regression gate
(``colors``/``valid`` quality fields plus ``work_ratio``, which
``check_regression.py`` holds above the baseline floor).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import SCALE, row

CHURN = 0.01
CHURN_GRAPHS = ("rmat-g", "G3_circuit", "europe.osm")


def _churn_once(name: str, scale: float, rounds: int = 4,
                backend: str | None = None, trace: bool = False):
    """One graph's churn record: steady-state round times + work accounting.

    Per-round wall is the MIN across rounds (the §14 pow2-shape padding
    makes round 1+ hit the jit cache, so the min is the steady-state serve
    cost and round 0 carries the one-time compile for both paths).

    ``trace=True`` (schema 6) opens the session with §16 tracing and adds
    ``rounds_detail`` — per round: frontier size, engine work, superstep
    count, tail-trigger step, and whether the recolor hit the jit cache —
    plus a ``jit`` hits/misses section from ``session.metrics()``.
    """
    from repro.core import color_data_driven
    from repro.dynamic import churn_delta, open_session
    from repro.graphs import build_graph

    g = build_graph(name, scale)
    rng = np.random.default_rng(14)
    session = open_session(g, backend=backend, trace=trace)
    w_inc = w_cold = frontier = 0
    t_inc, t_cold = [], []
    valid = True
    detail = []
    prev_hits = 0
    for i in range(rounds):
        rem, add = churn_delta(session.graph, CHURN, rng)
        dirty = session.apply_delta(remove_edges=rem, add_edges=add)
        frontier += int(dirty.size)
        t0 = time.perf_counter()
        inc = session.recolor()
        t_inc.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        cold = color_data_driven(session.graph, mode="fused",
                                 backend=backend)
        t_cold.append(time.perf_counter() - t0)
        w_inc += inc.work_items
        w_cold += cold.work_items
        valid &= session.validate()
        if trace:
            m = session.metrics()
            hit = m["engine_cache_hits"] > prev_hits
            prev_hits = m["engine_cache_hits"]
            detail.append({
                "round": i,
                "frontier": int(dirty.size),
                "work": int(inc.work_items),
                "supersteps": int(inc.trace.iterations),
                "tail_step": inc.trace.tail_step,
                "cache_hit": bool(hit),
            })
    rec = {
        "n": g.n,
        "m": g.m,
        "churn": CHURN,
        "rounds": rounds,
        "frontier": frontier,
        "colors": session.num_colors,
        "valid": bool(valid),
        "work_inc": int(w_inc),
        "work_cold": int(w_cold),
        "work_ratio": round(w_cold / max(w_inc, 1), 2),
        "seconds_inc": round(min(t_inc), 6),
        "seconds_cold": round(min(t_cold), 6),
        "degradations": [dict(d) for d in
                         getattr(session.result, "degradations", ())],
    }
    if not trace:
        return rec
    m = session.metrics()
    rec["rounds_detail"] = detail
    rec["jit"] = {"hits": m["engine_cache_hits"],
                  "misses": m["engine_cache_misses"]}
    return rec


def bench_dynamic_churn():
    """CSV rows: per-round incremental vs cold wall on the churn suite."""
    rows = []
    for name in CHURN_GRAPHS:
        r = _churn_once(name, SCALE)
        rows.append(row(f"dynamic_inc_{name}", r["seconds_inc"],
                        f"work_ratio={r['work_ratio']}"))
        rows.append(row(f"dynamic_cold_{name}", r["seconds_cold"],
                        f"colors={r['colors']}"))
    return rows


def bench_dynamic_json(scale: float, backend: str | None = None):
    """The ``dynamic`` BENCH section (schema 6): one churn record per suite
    graph, with per-round detail and jit accounting."""
    return {name: _churn_once(name, scale, backend=backend, trace=True)
            for name in CHURN_GRAPHS}


DYNAMIC_BENCHES = (bench_dynamic_churn,)
