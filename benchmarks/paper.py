"""One benchmark per paper table/figure (see DESIGN.md §8 for the mapping).

Every function returns rows (name, us_per_call, derived).  Quality numbers
(colors, iterations) are hardware-independent and reproduce the paper's
claims directly; runtimes are CPU-host wall-clock (the serial oracle runs on
the same host, so the *ratios* are the meaningful quantity, as in the paper).

This module IS wired into the harness (audited for PR §16): ``run.py``'s
CSV matrix iterates ``ALL_BENCHES`` on every non ``--json-only`` run, and
the weekly CI job (``--scale small`` without ``--json-only``) executes the
full set.  Step-level telemetry for these runs lives in the schema-6 JSON
documents (``trace`` sections), not in the CSV rows.
"""
from __future__ import annotations

import numpy as np

from benchmarks.batch import bench_batch_throughput
from benchmarks.common import SCALE, row, timeit
from repro.core import (
    color_data_driven,
    color_jp,
    color_multihash,
    color_threestep,
    color_topology,
    greedy_serial,
    is_valid_coloring,
    num_colors,
)
from repro.graphs import build_graph, build_suite, rmat
from repro.graphs.rmat import RMAT_ER, RMAT_G

# representative subset used by per-figure micro benches (full suite: fig8/9)
CORE_GRAPHS = ("rmat-er", "rmat-g", "G3_circuit", "cage15", "europe.osm")

_CACHE: dict = {}


def _graph(name, scale=None):
    key = (name, scale or SCALE)
    if key not in _CACHE:
        _CACHE[key] = build_graph(name, scale or SCALE)
    return _CACHE[key]


def _serial_time(g):
    t, colors = timeit(lambda: greedy_serial(g))
    return t, colors


# --------------------------------------------------------------------------
def bench_fig1_motivation():
    """Fig. 1: 3-step GM vs csrcolor(multi-hash MIS): speed AND quality."""
    rows = []
    for name in ("rmat-er", "rmat-g", "G3_circuit"):
        g = _graph(name)
        ts, base = _serial_time(g)
        t3, r3 = timeit(lambda: color_threestep(g))
        tm, rm = timeit(lambda: color_multihash(g, 2))
        rows.append(row(f"fig1/{name}/threestep_speedup", t3, round(ts / t3, 2)))
        rows.append(row(f"fig1/{name}/multihash_speedup", tm, round(ts / tm, 2)))
        rows.append(row(f"fig1/{name}/colors_serial", ts, num_colors(base)))
        rows.append(row(f"fig1/{name}/colors_threestep", t3, r3.num_colors))
        rows.append(row(f"fig1/{name}/colors_multihash", tm, rm.num_colors))
    return rows


def bench_table1_suite():
    """Table 1: the benchmark-graph suite (scaled stand-ins) + stats."""
    rows = []
    for name, g in build_suite(SCALE).items():
        rows.append(row(
            f"table1/{name}", 0.0,
            f"n={g.n};m={g.m};dbar={g.avg_degree:.1f};sigma={g.degree_std:.1f}",
        ))
    return rows


def bench_fig3_mapping():
    """Fig. 3: topology-driven vs data-driven runtime (normalized to serial)."""
    rows = []
    for name in CORE_GRAPHS:
        g = _graph(name)
        ts, _ = _serial_time(g)
        tt, rt = timeit(lambda: color_topology(g, heuristic="id"))
        td, rd = timeit(lambda: color_data_driven(g, heuristic="id"))
        rows.append(row(f"fig3/{name}/topo_speedup", tt, round(ts / tt, 2)))
        rows.append(row(f"fig3/{name}/data_speedup", td, round(ts / td, 2)))
        rows.append(row(f"fig3/{name}/work_ratio_topo_over_data", 0.0,
                        round(rt.work_items / max(rd.work_items, 1), 2)))
    return rows


def bench_fig4_heuristic():
    """Fig. 4: iterations to converge, id-rule vs degree-heuristic."""
    rows = []
    for name in CORE_GRAPHS:
        g = _graph(name)
        tb, rb = timeit(lambda: color_data_driven(g, heuristic="id"))
        th, rh = timeit(lambda: color_data_driven(g, heuristic="degree"))
        rows.append(row(f"fig4/{name}/iters_baseline", tb, rb.iterations))
        rows.append(row(f"fig4/{name}/iters_heuristic", th, rh.iterations))
        rows.append(row(f"fig4/{name}/speedup_over_baseline", th,
                        round(tb / th, 2)))
    return rows


def bench_fig5_coarsening():
    """Fig. 5: thread coarsening on FirstFit (TC-ff), ConflictResolve (TC-cr), both."""
    rows = []
    for name in ("G3_circuit", "cage15", "rmat-g"):
        g = _graph(name)
        t0, _ = timeit(lambda: color_data_driven(g))
        for label, kw in (
            ("tc_ff", dict(coarsen_ff=4)),
            ("tc_cr", dict(coarsen_cr=4)),
            ("tc_both", dict(coarsen_ff=4, coarsen_cr=4)),
            ("tc_lanes16k", dict(coarsen_lanes=16384)),
        ):
            t, r = timeit(lambda: color_data_driven(g, **kw))
            rows.append(row(f"fig5/{name}/{label}_speedup", t,
                            round(t0 / t, 2)))
            rows.append(row(f"fig5/{name}/{label}_iters", t, r.iterations))
    return rows


def bench_fig6_bitset():
    """Fig. 6: FirstFit operator — colorMask scan vs sort vs bitset (+Pallas)."""
    rows = []
    for name in ("rmat-er", "rmat-g", "thermal2"):
        g = _graph(name)
        t_scan, _ = timeit(lambda: color_data_driven(g, firstfit="scan"))
        t_sort, _ = timeit(lambda: color_data_driven(g, firstfit="sort"))
        t_bit, _ = timeit(lambda: color_data_driven(g, firstfit="bitset"))
        rows.append(row(f"fig6/{name}/bitset_vs_scan", t_bit,
                        round(t_scan / t_bit, 2)))
        rows.append(row(f"fig6/{name}/bitset_vs_sort", t_bit,
                        round(t_sort / t_bit, 2)))
    # isolated kernel comparison on a fixed padded worklist (interpret mode)
    import jax.numpy as jnp
    from repro.core.firstfit import FF_FUNCS
    from repro.kernels.firstfit.ops import firstfit_bitset_tpu

    rng = np.random.default_rng(0)
    nc = jnp.asarray(rng.integers(0, 40, size=(4096, 32)).astype(np.int32))
    for kind, fn in FF_FUNCS.items():
        t, _ = timeit(lambda: fn(nc).block_until_ready())
        rows.append(row(f"fig6/kernel_{kind}", t, "jnp"))
    t, _ = timeit(lambda: firstfit_bitset_tpu(nc).block_until_ready())
    rows.append(row("fig6/kernel_bitset_pallas_interp", t, "interpret=True"))
    return rows


def bench_fig7_common():
    """Fig. 7: kernel fusion (fused device loop), __ldg (N/A on TPU — VMEM
    staging is explicit), and Merrill-style load balancing (degree buckets).

    Fusion (the single-device-program mode) is timed on regular graphs only:
    on this CPU host its full-capacity super-steps are slow for skewed graphs
    (on TPU the wide vector lanes are the point); load balancing is timed on
    the skewed graphs where it matters.
    """
    rows = []
    for name in ("rmat-er", "thermal2"):
        g = _graph(name)
        t0, _ = timeit(lambda: color_data_driven(g))
        tf, _ = timeit(lambda: color_data_driven(g, mode="fused"))
        rows.append(row(f"fig7/{name}/fusion_speedup", tf, round(t0 / tf, 2)))
    for name in ("rmat-g", "cage15", "kkt_power"):
        g = _graph(name)
        t0, _ = timeit(lambda: color_data_driven(g))
        tl, rl = timeit(lambda: color_data_driven(g, buckets=(16, 128)))
        rows.append(row(f"fig7/{name}/loadbalance_speedup", tl,
                        round(t0 / tl, 2)))
    rows.append(row("fig7/ldg", 0.0, "N/A-on-TPU(BlockSpec-VMEM-staging)"))
    return rows


def bench_fig8_quality():
    """Fig. 8: total colors assigned per implementation per graph."""
    rows = []
    for name, g in build_suite(SCALE).items():
        rows.append(row(f"fig8/{name}/serial", 0.0, num_colors(greedy_serial(g))))
        for label, fn in (
            ("proposed_opt", lambda: color_data_driven(g)),
            ("proposed_base", lambda: color_data_driven(
                g, heuristic="id", firstfit="scan")),
            ("jp", lambda: color_jp(g)),
            ("csrcolor_multihash", lambda: color_multihash(g, 2)),
        ):
            r = fn()
            assert is_valid_coloring(g, r.colors), (name, label)
            rows.append(row(f"fig8/{name}/{label}", 0.0, r.num_colors))
    return rows


def bench_fig9_speedup():
    """Fig. 9: end-to-end runtime speedup over Serial, all implementations."""
    rows = []
    speedups = {"proposed_base": [], "proposed_opt": [], "csrcolor": [],
                "threestep": []}
    for name, g in build_suite(SCALE).items():
        ts, _ = _serial_time(g)
        for label, fn in (
            ("proposed_base", lambda: color_data_driven(
                g, heuristic="id", firstfit="scan")),
            ("proposed_opt", lambda: color_data_driven(
                g, heuristic="degree", firstfit="bitset",
                coarsen_lanes=16384, buckets=(16, 128))),
            ("csrcolor", lambda: color_multihash(g, 2)),
            ("threestep", lambda: color_threestep(g)),
        ):
            t, _ = timeit(fn)
            s = ts / t
            speedups[label].append(s)
            rows.append(row(f"fig9/{name}/{label}", t, round(s, 2)))
    for label, vals in speedups.items():
        rows.append(row(f"fig9/geomean/{label}", 0.0,
                        round(float(np.exp(np.mean(np.log(vals)))), 2)))
    return rows


def bench_fig10_scaling():
    """Fig. 10: |V| sweep at fixed dbar=10 (rmat-er), speedup vs serial."""
    rows = []
    for logn in (13, 14, 15, 16):
        g = rmat(1 << logn, 10.0, RMAT_ER, seed=42)
        ts, _ = _serial_time(g)
        t, r = timeit(lambda: color_data_driven(g))
        rows.append(row(f"fig10/n=2^{logn}", t, round(ts / t, 2)))
    return rows


def bench_fig11_density():
    """Fig. 11: average-degree sweep at fixed |V| (rmat-er)."""
    rows = []
    n = 16384
    for dbar in (2, 5, 10, 20, 40):
        g = rmat(n, float(dbar), RMAT_ER, seed=43)
        ts, _ = _serial_time(g)
        to, ro = timeit(lambda: color_data_driven(g))
        tb, _ = timeit(lambda: color_data_driven(g, heuristic="id",
                                                 firstfit="scan"))
        rows.append(row(f"fig11/dbar={dbar}/opt", to, round(ts / to, 2)))
        rows.append(row(f"fig11/dbar={dbar}/base", tb, round(ts / tb, 2)))
        rows.append(row(f"fig11/dbar={dbar}/iters", 0.0, ro.iterations))
    return rows


def bench_fig12_ragged_engine():
    """§12: the ragged CSR-native super-step engine vs the classic two-phase.

    ``superstep_speedup`` is the acceptance metric — wall time of ONE
    degree-tiled fused super-step (one gather pair, one dispatch) vs one
    classic FirstFit+ConflictResolve super-step (two gather pairs) on the
    same full worklist, post-warmup.  ``engine_speedup`` is end-to-end; on
    the cascading circuit graphs the adaptive tail-serialization collapses
    hundreds of super-steps into ~4.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.coloring import (_resolve_classes, provider_tiled_superstep,
                                     sgr_step)
    from repro.core.csr import DeviceCSR

    rows = []
    for name in ("rmat-g", "rmat-er"):
        g = _graph(name)
        n = g.n
        dcsr = DeviceCSR.from_csr(g)
        adj = jnp.asarray(g.padded_adjacency())
        deg_ext = jnp.asarray(
            np.concatenate([g.degrees, np.zeros(1, np.int32)]).astype(np.int32))
        colors = jnp.where(
            jnp.arange(n + 1, dtype=jnp.int32) < n, 1, 0).astype(jnp.int32)
        wl = jnp.arange(n, dtype=jnp.int32)
        classes, widths = _resolve_classes(g.degrees, (), "auto")
        wls = tuple(jnp.asarray(c) for c in classes)
        t_cl, _ = timeit(lambda: jax.block_until_ready(
            sgr_step(adj, deg_ext, colors, wl,
                     heuristic="degree", kind="bitset")))
        t_rg, _ = timeit(lambda: jax.block_until_ready(
            provider_tiled_superstep(
                dcsr, deg_ext, colors, wls, widths=tuple(widths),
                heuristic="degree", kind="bitset", use_kernel=False,
                chunks=(1,) * len(wls))))
        rows.append(row(f"fig12/{name}/superstep_speedup", t_rg,
                        round(t_cl / t_rg, 2)))
        # classic step: 2 adjacency + 2 color + 1 degree tile at full width;
        # rotated step: 1 adjacency + 1 packed color|degree tile per class
        rows.append(row(f"fig12/{name}/superstep_gather_cells_ratio", 0.0,
                        round(5 * n * g.max_degree /
                              max(2 * sum(len(c) * w for c, w in
                                          zip(classes, widths)), 1), 2)))
    for name in ("rmat-g", "G3_circuit", "thermal2", "europe.osm"):
        g = _graph(name)
        tc, rc = timeit(lambda: color_data_driven(g, engine="classic"))
        tr, rr = timeit(lambda: color_data_driven(g))
        assert is_valid_coloring(g, rr.colors), name
        rows.append(row(f"fig12/{name}/engine_speedup", tr,
                        round(tc / tr, 2)))
        rows.append(row(f"fig12/{name}/iters_classic_vs_ragged", tr,
                        f"{rc.iterations}->{rr.iterations}"))
        rows.append(row(f"fig12/{name}/colors_classic_vs_ragged", tr,
                        f"{rc.num_colors}->{rr.num_colors}"))
    return rows


ALL_BENCHES = [
    bench_fig1_motivation,
    bench_table1_suite,
    bench_fig3_mapping,
    bench_fig4_heuristic,
    bench_fig5_coarsening,
    bench_fig6_bitset,
    bench_fig7_common,
    bench_fig8_quality,
    bench_fig9_speedup,
    bench_fig10_scaling,
    bench_fig11_density,
    bench_fig12_ragged_engine,
    bench_batch_throughput,
]
