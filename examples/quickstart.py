"""Quickstart: color a sparse graph with every implementation and compare.

    PYTHONPATH=src python examples/quickstart.py [--scale 0.2]

Reproduces the paper's headline result in one screen: the data-driven
speculative-greedy implementation matches serial greedy quality while the
MIS/multi-hash (csrcolor) baseline burns several times more colors.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    color_data_driven,
    color_jp,
    color_multihash,
    color_threestep,
    color_topology,
    greedy_serial,
    is_valid_coloring,
    num_colors,
)
from repro.graphs import rmat  # noqa: E402
from repro.graphs.rmat import RMAT_G  # noqa: E402


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--degree", type=float, default=10.0)
    args = ap.parse_args()

    g = rmat(args.n, args.degree, RMAT_G, seed=0)
    print(f"graph: n={g.n} m={g.m} dbar={g.avg_degree:.1f} "
          f"maxdeg={g.max_degree}\n")

    t0 = time.perf_counter()
    serial = greedy_serial(g)
    t_serial = time.perf_counter() - t0
    print(f"{'algorithm':28s} {'colors':>6s} {'iters':>5s} {'time':>8s} "
          f"{'speedup':>7s} valid")

    def report(name, colors, iters, t):
        ok = is_valid_coloring(g, colors)
        print(f"{name:28s} {num_colors(colors):6d} {iters:5d} {t*1e3:7.1f}ms "
              f"{t_serial/t:7.2f} valid={ok}")

    report("serial greedy (oracle)", serial, g.n, t_serial)
    for name, fn in [
        ("proposed-opt (SGR)", lambda: color_data_driven(g, coarsen_lanes=16384)),
        ("proposed-base (SGR)", lambda: color_data_driven(
            g, heuristic="id", firstfit="scan")),
        ("topology-driven", lambda: color_topology(g)),
        ("3-step GM analogue", lambda: color_threestep(g)),
        ("JP (MIS)", lambda: color_jp(g)),
        ("csrcolor multi-hash (MIS)", lambda: color_multihash(g, 2)),
    ]:
        r = fn()  # warmup/compile
        t0 = time.perf_counter()
        r = fn()
        report(name, r.colors, r.iterations, time.perf_counter() - t0)


if __name__ == "__main__":
    main()
