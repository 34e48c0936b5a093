"""Run every paper benchmark. Prints ``name,us_per_call,derived`` CSV and
writes ``BENCH_coloring.json`` — the machine-readable perf trajectory.

Scale via ``--scale {tiny,small,paper}`` or REPRO_BENCH_SCALE (default 0.15);
see benchmarks/common.py.  The roofline table (§Roofline) is separate:
``python -m benchmarks.roofline`` consumes the dry-run JSON produced by
``repro.launch.dryrun``.

``BENCH_coloring.json`` records per-algorithm colors + wall-clock on a small
fixed suite (REPRO_BENCH_JSON_SCALE, default 0.02) so CI and future PRs can
diff quality/perf without parsing the CSV.  Timing method (schema 2+):
``seconds`` is the MEDIAN of post-warmup calls and ``compile_seconds`` the
separately-measured one-time jit cost — single-shot numbers used to charge
compilation to the algorithm.  ``--json-only`` skips the CSV matrix.

Schema 3 adds ``--engine {ragged,padded,classic,sharded}``: the chosen
engine is threaded through the algorithms that take one (``data_driven``,
``fused``; ``distance2`` for ragged/sharded), the document carries a
top-level ``engine`` field plus per-record ``engine`` /
``halo_bytes_per_step`` (§13 halo traffic; 0 off the sharded engine).  Run
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise
``sharded`` on simulated devices — CI's sharded bench-smoke artifact is
``BENCH_coloring_sharded.json``.  ``--engine sharded`` REFUSES to run on a
single-device host (the engine would silently fall back to ``ragged`` and
the recorded numbers would come from the wrong engine).

Schema 4 adds ``--engine dynamic`` (§14): instead of the algorithm matrix
the document carries a ``dynamic`` section of churn records — per suite
graph, incremental ``session.recolor()`` vs cold re-color work/wall under
1% streaming edge churn (``benchmarks/dynamic.py``).  CI's artifact is
``BENCH_coloring_dynamic.json``; ``benchmarks/check_regression.py`` gates
every produced document against ``benchmarks/baseline_tiny.json``.

Schema 5 adds ``--backend {jax,pallas}`` (§15): the chosen backend is
threaded through the algorithms that take one (``data_driven``, ``fused``,
``distance2``, ``dynamic``), the document carries a top-level ``backend``
field, and every record whose result reports per-degree-class work counters
(``ColoringResult.class_cells``) embeds a ``roofline`` section — bytes
moved and achieved bytes/s per degree class (``benchmarks/roofline.py``'s
coloring model).  Colors are bit-identical across backends, so the pallas
document gates against the SAME baseline; CI's artifact is
``BENCH_coloring_pallas.json``.

Schema 6 adds the §16 telemetry: every record of an algorithm that takes
the ``trace=`` knob (``BACKEND_ALGS``) carries a ``trace`` section — the
``RunTrace.summary()`` per-step series (live/retired/conflicts/max_color/
cells), superstep count, and tail-trigger step — captured from one extra
UNTIMED traced call so the timed numbers stay on the untraced (bit-
identical, zero-cost) path.  ``--engine dynamic`` records gain
``rounds_detail`` (per churn round: frontier, work, supersteps, tail step,
jit cache hit) and a ``jit`` hits/misses section from
``session.metrics()``; ``python -m repro.obs.report <file>`` re-renders
them.  ``benchmarks/check_regression.py`` gates the new sections: missing
trace, superstep-count regressions, earlier tail triggers, broken row
invariants, and dynamic jit-miss growth all fail CI.

Schema 8 adds ``--backend pallas-csr`` (§18, the CSR-resident fused
kernel) and an honest per-backend roofline traffic model: the legacy
``pallas`` backend is charged its REAL traffic — the host-side gather
materializes split-size tiles in HBM and the kernel reads them back
(24 B/cell) — while ``pallas-csr`` gathers id + packed word straight
from the CSR arrays (8 B/cell).  Every roofline class entry now carries
its own ``bytes_per_cell`` and the section a ``mode`` field, so the
pallas vs pallas-csr delta is visible per degree class.  Colors stay
bit-identical across all backends; CI's artifact is
``BENCH_coloring_pallas_csr.json``, gated against the same baseline.
"""
from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)  # so `python benchmarks/run.py` finds `benchmarks.*`

JSON_PATH = os.environ.get("REPRO_BENCH_JSON", "BENCH_coloring.json")
JSON_GRAPHS = ("rmat-er", "rmat-g", "G3_circuit", "europe.osm", "thermal2")

# --scale presets: (CSV-matrix scale, JSON-suite scale).  ``tiny`` is the CI
# smoke configuration — its JSON scale is pinned at 0.01 so the uploaded
# BENCH_coloring.json artifacts stay comparable across CI runs (the file
# itself is a generated artifact, gitignored); ``paper`` matches the default
# full matrix.
SCALE_PRESETS = {
    "tiny": (0.02, 0.01),
    "small": (0.05, 0.02),
    "paper": (0.15, 0.02),
}


def _engine_opts(alg: str, engine: str) -> dict:
    """The engine kwargs ``alg`` understands (empty when it takes none)."""
    if alg in ("data_driven", "fused"):
        return {"engine": engine}
    if alg == "distance2" and engine in ("ragged", "sharded"):
        return {"engine": engine}
    return {}


# algorithms that accept the §15 backend= knob (kernel vs pure-JAX superstep)
BACKEND_ALGS = ("data_driven", "fused", "distance2", "dynamic")
BACKENDS = ("jax", "pallas", "pallas-csr")

# roofline traffic model per backend (schema 8): the gathered-tile pallas
# path materializes split tiles in HBM and reads them back; the CSR kernel
# reads id + packed word once from R/C; pure JAX uses the packed gather
_ROOFLINE_MODE = {"pallas": "pallas", "pallas-csr": "csr"}


def _backend_opts(alg: str, backend: str) -> dict:
    """The backend kwarg for ``alg`` (empty when it takes none)."""
    return {"backend": backend} if alg in BACKEND_ALGS else {}


def bench_coloring_json(path: str = JSON_PATH, engine: str = "ragged",
                        backend: str = "jax") -> dict:
    """Per-algorithm colors + wall-clock on the small suite, as JSON."""
    from benchmarks.common import timeit_median
    from benchmarks.roofline import coloring_roofline
    from repro import api
    from repro.core import is_valid_coloring
    from repro.d2 import compress_jacobian_pattern, validate_bipartite
    from repro.graphs import build_graph, jacobian_band

    json_scale = float(os.environ.get("REPRO_BENCH_JSON_SCALE", "0.02"))
    graphs = {name: build_graph(name, json_scale) for name in JSON_GRAPHS}
    doc = {
        "schema": 8,
        "scale": json_scale,
        "engine": engine,
        "backend": backend,
        "graphs": {
            name: {"n": g.n, "m": g.m, "max_degree": g.max_degree}
            for name, g in graphs.items()
        },
        "algorithms": {},
        "bipartite": {},
    }
    for alg in api.algorithms():
        if alg == "bipartite":  # needs a BipartiteGraph; measured below
            continue
        opts = {**_engine_opts(alg, engine), **_backend_opts(alg, backend)}
        per_graph = {}
        for name, g in graphs.items():
            try:
                seconds, compile_s, r = timeit_median(
                    lambda: api.color(g, algorithm=alg, **opts))
            except Exception as e:  # keep the harness going
                per_graph[name] = {"error": f"{type(e).__name__}: {e}"}
                continue
            rec = {
                "colors": r.num_colors,
                "seconds": round(seconds, 6),
                "compile_seconds": round(compile_s, 6),
                "iterations": r.iterations,
                "valid": bool(is_valid_coloring(g, r.colors)),
                "engine": opts.get("engine", "-"),
                "backend": opts.get("backend", "-"),
                "halo_bytes_per_step": round(
                    getattr(r, "halo_bytes_per_step", 0.0), 1),
                # §17 robustness ledger: non-empty means the run left the
                # clean fast path; the CI gate fails on unexpected stages
                "degradations": [dict(d) for d in
                                 getattr(r, "degradations", ())],
            }
            if getattr(r, "class_cells", ()):
                rec["roofline"] = coloring_roofline(
                    r, seconds, mode=_ROOFLINE_MODE.get(backend, "packed"))
            if alg in BACKEND_ALGS:
                # one extra UNTIMED traced call (schema 6): the timed
                # numbers above stay on the untraced zero-cost path
                rt = api.color(g, algorithm=alg, trace=True, **opts).trace
                if rt is not None:
                    rec["trace"] = rt.summary()
            per_graph[name] = rec
        doc["algorithms"][alg] = per_graph
    band = 2
    bg = jacobian_band(int(20000 * json_scale) or 64, band=band)
    seconds, compile_s, cr = timeit_median(
        lambda: compress_jacobian_pattern(bg, mode="fused"))
    doc["bipartite"][f"banded_b{band}"] = {
        "groups": cr.num_groups,
        "optimal": 2 * band + 1,
        "seconds": round(seconds, 6),
        "compile_seconds": round(compile_s, 6),
        "valid": bool(validate_bipartite(bg, cr.coloring.colors)),
        "degradations": [dict(d) for d in
                         getattr(cr.coloring, "degradations", ())],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


ENGINES = ("ragged", "padded", "classic", "sharded", "dynamic")


def bench_dynamic_json_doc(path: str = JSON_PATH,
                           backend: str = "jax") -> dict:
    """The ``--engine dynamic`` document: §14 churn records, no matrix."""
    from benchmarks.dynamic import bench_dynamic_json

    json_scale = float(os.environ.get("REPRO_BENCH_JSON_SCALE", "0.02"))
    records = bench_dynamic_json(json_scale, backend=backend)
    doc = {
        "schema": 8,
        "scale": json_scale,
        "engine": "dynamic",
        "backend": backend,
        "dynamic": records,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def main() -> None:
    args = sys.argv[1:]
    if "--scale" in args:
        tail = args[args.index("--scale") + 1:]
        preset = tail[0] if tail else None
        if preset not in SCALE_PRESETS:
            raise SystemExit(
                f"unknown --scale {preset!r}; options: {sorted(SCALE_PRESETS)}")
        csv_scale, json_scale = SCALE_PRESETS[preset]
        # set BEFORE benchmarks.common/paper are imported (they read at import)
        os.environ["REPRO_BENCH_SCALE"] = str(csv_scale)
        os.environ["REPRO_BENCH_JSON_SCALE"] = str(json_scale)
    engine = "ragged"
    if "--engine" in args:
        tail = args[args.index("--engine") + 1:]
        engine = tail[0] if tail else None
        if engine not in ENGINES:
            raise SystemExit(
                f"unknown --engine {engine!r}; options: {list(ENGINES)}")
    backend = "jax"
    if "--backend" in args:
        tail = args[args.index("--backend") + 1:]
        backend = tail[0] if tail else None
        if backend not in BACKENDS:
            raise SystemExit(
                f"unknown --backend {backend!r}; options: {list(BACKENDS)}")
    if engine == "sharded":
        # the api would silently fall back to the single-device ragged
        # engine — refuse instead, so recorded bench numbers can never come
        # from the wrong engine (CI forces a simulated fleet via XLA_FLAGS)
        import jax

        if jax.device_count() <= 1:
            raise SystemExit(
                "--engine sharded needs a multi-device host but only "
                f"{jax.device_count()} device is visible; run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8 (or on "
                "real multi-device hardware) so the sharded engine actually "
                "executes instead of falling back to ragged")
    json_only = "--json-only" in args
    if not json_only:
        from benchmarks.d2 import D2_BENCHES
        from benchmarks.dynamic import DYNAMIC_BENCHES
        from benchmarks.paper import ALL_BENCHES

        print("name,us_per_call,derived", flush=True)
        for bench in (list(ALL_BENCHES) + list(D2_BENCHES)
                      + list(DYNAMIC_BENCHES)):
            t0 = time.time()
            try:
                rows = bench()
            except Exception as e:  # keep the harness going; report the failure
                print(f"{bench.__name__},0,ERROR:{type(e).__name__}:{e}")
                continue
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}", flush=True)
            print(f"# {bench.__name__} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
    if engine == "dynamic":
        bench_dynamic_json_doc(backend=backend)
    else:
        bench_coloring_json(engine=engine, backend=backend)
    print(f"# wrote {JSON_PATH} (engine={engine}, backend={backend})",
          file=sys.stderr)


if __name__ == "__main__":
    main()
