"""The traffic drivers: set-up, warm-up, the measured window, the check.

A traffic mix (``traffic/<mix>.json``) names its driver and gives its
parameters; a driver takes a ``harness.Run`` and returns

* ``attempted`` / ``failed``: requests in the window, and those that
  raised;
* ``end_to_end``: the cell's end-to-end values other than ``setup_s``;
* ``layer``: what the per-layer readers read (counts);
* ``check``: a function the harness calls after the window, the memory
  reading and the trace, which compares every answer of the window with
  the plain reference and returns ``{name: {"value", "limit"}}``.

A request that raises is counted and the window goes on; its
answer never comes, so the check counts every vertex of it as uncolored.
"""
from __future__ import annotations

import numpy as np

from bench import reference

# Each number compared, with its limit.  A proper coloring leaves no vertex
# improper, and this is an exact count: the limit is 0.
LIMITS = {"improper_vertices": 0}


def _compared(improper: int) -> dict:
    return {"improper_vertices": {"value": int(improper),
                                  "limit": LIMITS["improper_vertices"]}}


def oneshot(run) -> dict:
    """One caller colors the run's graphs, closed loop, in whole cycles over
    ``run.graphs``.  Every request hands ``repro.color`` a fresh
    ``CSRGraph`` over the generated arrays, so it pays for the CSR build and
    upload as a user coloring a graph once does, and ends with the colors on
    the host.  Set-up colors each graph once: orders can reach different
    program shapes, and none may compile inside the window."""
    graphs, traffic = run.graphs, run.traffic
    options = dict(traffic.get("options", {}))

    def request(g):
        with run.annotate("color"):
            r = run.repro.color(run.CSRGraph(g.row_offsets, g.col_indices),
                                traffic["algorithm"], **options)
        with run.annotate("readback"):
            colors = np.array(r.colors)
        return r, colors

    with run.annotate("warmup"):
        for g in graphs:
            request(g)
    answers, iterations, num_colors, failed = [], [], [], 0
    with run.window():
        while True:
            g = graphs[len(answers) % len(graphs)]
            try:
                r, colors = request(g)
            except Exception as e:  # a failed request counts, the window goes on
                run.note_failure(e)
                failed += 1
                answers.append(None)
            else:
                answers.append(colors)
                iterations.append(int(r.iterations))
                num_colors.append(int(colors.max(initial=0)))
            if run.done(len(answers)):
                break

    def check():
        improper = 0
        for i, g in enumerate(graphs):
            src, dst = g.undirected_edges()
            improper += sum(
                g.n if c is None
                else reference.improper_vertices(g.n, src, dst, c)
                for c in answers[i::len(graphs)])
        return _compared(improper)

    return {
        "attempted": len(answers),
        "failed": failed,
        "end_to_end": {"coloring_s": run.window_s / len(answers)},
        "layer": {"requests": len(answers), "iterations": iterations,
                  "colors": num_colors},
        "check": check,
    }


DRIVERS = {"oneshot": oneshot}
