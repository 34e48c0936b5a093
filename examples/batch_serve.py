"""Serving-path demo: a ColoringService micro-batching a request stream.

    PYTHONPATH=src python examples/batch_serve.py [--requests 24] [--batch 16]

The ROADMAP serving scenario, served for real (§19): many users submit
graphs to a shared ``ColoringService``; its worker drains the bounded
request queue in micro-batches, buckets requests by ``(pow2 shape class,
ColorOptions)``, and colors every bucket with ONE jitted device program
(``core/batch.py``).  Every response is validated and bit-identical to
the per-request fused path, steady traffic stays inside the jit cache
(zero misses after the first wave), and a closing flood shows the
backpressure contract: a full queue rejects with a structured
``Overloaded`` instead of growing without bound.

Telemetry comes from the service itself (§16 x §19): ``service.metrics()``
(micro-batch and jit-cache accounting) and ``take_spans()`` (per-request /
per-micro-batch spans from the worker loop), plus one untimed traced
re-run of the first requests for the per-super-step table.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import repro  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import is_valid_coloring  # noqa: E402
from repro.core.batch import color_batch_fused  # noqa: E402
from repro.errors import Overloaded  # noqa: E402
from repro.graphs import serving_mix  # noqa: E402
from repro.launch.coloring_service import ColoringService  # noqa: E402
from repro.obs.report import format_result, format_trace  # noqa: E402


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--waves", type=int, default=3)
    args = ap.parse_args()

    graphs = serving_mix(args.requests, scale=0.25)
    print(f"{args.requests} coloring requests/wave x {args.waves} waves, "
          f"micro-batch window B={args.batch}\n")

    # ---- reference: warm per-request loop, one device program each ----------
    for g in graphs:
        repro.color(g, "fused")    # warm every shape's jit cache
    t0 = time.perf_counter()
    loop_results = [repro.color(g, "fused") for g in graphs]
    t_loop = time.perf_counter() - t0

    with ColoringService(queue_limit=max(64, 2 * args.requests),
                         max_batch=args.batch, trace=True) as svc:
        # ---- warmup wave: presents every (bucket, pow2 B) key once ----------
        for t in [svc.color(g, wait=False) for g in graphs]:
            t.wait(120)
        warm_misses = svc.metrics()["bucket_jit_misses"]

        # ---- steady waves: async bursts drain as bucketed micro-batches -----
        t0 = time.perf_counter()
        svc_results = []
        for _ in range(args.waves):
            tickets = [svc.color(g, wait=False) for g in graphs]
            svc_results.append([t.wait(120) for t in tickets])
        t_svc = (time.perf_counter() - t0) / args.waves
        m = svc.metrics()
        spans = svc.take_spans()

        # ---- overload: flood far past queue_limit, catch the rejections -----
        accepted, shed = [], 0
        for _ in range(4 * svc.metrics()["queue_limit"]):
            try:
                accepted.append(svc.color(graphs[0], wait=False))
            except Overloaded as e:
                shed += 1
                retry_after = e.retry_after
        for t in accepted:
            t.wait(120)

    ok = all(is_valid_coloring(g, r.colors)
             for wave in svc_results for g, r in zip(graphs, wave))
    identical = all((a.colors == b.colors).all()
                    for wave in svc_results
                    for a, b in zip(loop_results, wave))
    print(f"per-request loop : {t_loop * 1e3:8.1f} ms/wave   "
          f"{len(graphs) / t_loop:7.1f} graphs/sec")
    print(f"service          : {t_svc * 1e3:8.1f} ms/wave   "
          f"{len(graphs) / t_svc:7.1f} graphs/sec "
          f"(admission + batching + validation included)")
    print(f"all proper={ok}  bit-identical to loop={identical}")
    colors = sorted(r.num_colors for r in svc_results[0])
    print(f"colors used per graph: min={colors[0]} max={colors[-1]}")

    # ---- service telemetry (§19) --------------------------------------------
    mb = [e for e in spans if e.name == "serve_microbatch"]
    steady_misses = m["bucket_jit_misses"] - warm_misses
    print(f"\nservice: {m['microbatches']} micro-batches for "
          f"{m['batched_requests']} batched requests across "
          f"{len(m['buckets'])} buckets; jit misses after the warmup "
          f"wave: {steady_misses} (the §19 contract: steady traffic "
          "re-presents warm keys)")
    if mb:
        sizes = sorted(e.meta["B"] for e in mb)
        print(f"micro-batch sizes: min={sizes[0]} max={sizes[-1]} "
              f"({len(mb)} dispatches)")
    print(f"overload flood: {len(accepted)} accepted, {shed} shed with "
          f"structured Overloaded (retry_after~{retry_after:.3f}s); the "
          "queue never grew past its limit")

    # ---- per-super-step table: untimed traced re-run (§16) ------------------
    traced = color_batch_fused(graphs[: min(4, len(graphs))], trace=True)
    print("\nfirst requests, per request:")
    for i, r in enumerate(traced):
        print("  " + format_result(f"request[{i}]", r))
    print("\nrequest[0], per super-step:")
    print(format_trace(traced[0].trace))


if __name__ == "__main__":
    main()
