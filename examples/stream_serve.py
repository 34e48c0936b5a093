"""Streaming-serve demo: a service-hosted session over a mutating graph.

    PYTHONPATH=src python examples/stream_serve.py [--rounds 8] [--churn 0.01]

The ROADMAP streaming scenario, served for real (§19): a long-lived user
graph lives as a pooled session inside a ``ColoringService``; each round
a batch of edge updates (the churn fraction deleted, the same number
inserted) goes through ``service.apply_delta`` and a frontier-sized
``service.recolor`` repairs the coloring, while a naive server re-runs
the cold fused engine from scratch.  Both are validated every round and
the work/wall ratios are reported.  Compaction stays off the hot path
(deferred maintenance) and runs in one explicit ``service.maintain()``
lull at the end.

Reporting goes through ``repro.obs`` (§16): per-round lines come from
``format_result``, the closing blocks are ``service.session_metrics()``
via ``format_metrics`` plus the service's own counters, and the worker's
per-request spans are rendered with ``format_spans``.
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import color_data_driven, is_valid_coloring  # noqa: E402
from repro.dynamic import churn_delta  # noqa: E402
from repro.graphs import build_graph  # noqa: E402
from repro.launch.coloring_service import ColoringService  # noqa: E402
from repro.obs.report import format_metrics, format_result  # noqa: E402


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="G3_circuit")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--churn", type=float, default=0.01)
    args = ap.parse_args()
    rng = np.random.default_rng(0)

    g = build_graph(args.graph, args.scale)
    svc = ColoringService(pool_size=4, queue_limit=64, trace=True)
    sid = "user-0"
    opened = svc.open_session(sid, g)
    print(f"{args.graph}: n={g.n} m={g.m // 2} edges, "
          f"{args.churn:.1%} churn x {args.rounds} rounds "
          f"(session {sid!r}, pool {svc.metrics()['pool_occupancy']}/"
          f"{svc.metrics()['pool_size']})\n")
    print(f"cold start: {opened['num_colors']} colors, "
          f"converged={opened['converged']}\n")

    # the cold comparator recolors the same mutating graph from scratch;
    # track it on a live session handle so both sides see identical deltas
    live = svc._touch(sid)

    t_inc = t_cold = 0.0
    for r in range(args.rounds):
        rem, add = churn_delta(live.graph, args.churn, rng)

        t0 = time.perf_counter()
        td = svc.apply_delta(sid, remove_edges=rem, add_edges=add,
                             wait=False)
        inc = svc.recolor(sid)            # client waits for the repair
        dirty = td.wait()
        t_inc += time.perf_counter() - t0

        t0 = time.perf_counter()
        cold = color_data_driven(live.graph, mode="fused")
        t_cold += time.perf_counter() - t0

        ok = (is_valid_coloring(live.graph, np.asarray(svc.colors(sid)))
              and is_valid_coloring(live.graph, cold.colors))
        print(f"round {r}: frontier={dirty.size:5d}  valid={ok}")
        print("  " + format_result("inc ", inc))
        print("  " + format_result("cold", cold))

    print(f"\nwall: incremental={t_inc * 1e3:.0f} ms  "
          f"cold={t_cold * 1e3:.0f} ms  "
          f"speedup={t_cold / max(t_inc, 1e-9):.1f}x")

    # lull-time maintenance: compaction/snapshots deferred off the hot path
    done = svc.maintain(sid)
    print(f"maintenance at the lull: {done[sid] or 'nothing due'}")

    print(format_metrics(svc.session_metrics(sid), "\nsession metrics:"))
    m = svc.metrics()
    print(f"\nservice: {m['admitted']} admitted, {m['completed']} completed, "
          f"{m['rejected']} rejected, queue peak depth <= "
          f"{m['queue_limit']}, engine cache "
          f"{m['session_engine_cache_hits']} hits / "
          f"{m['session_engine_cache_misses']} misses")
    spans = svc.take_spans()
    kinds = {}
    for e in spans:
        kinds[e.name] = kinds.get(e.name, 0) + 1
    print("worker spans: " + ", ".join(f"{k} x{v}"
                                       for k, v in sorted(kinds.items())))
    svc.shutdown()


if __name__ == "__main__":
    main()
