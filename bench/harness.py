"""The benchmark's general part: specs, graphs, window, trace and result.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json``: the deployment (generator and its parameters);
* ``graphs/<generator>.py``: ``generate(params, rng) -> Graph``;
* ``traffic/<mix>.json``: a traffic mix, parameters for one of the
  drivers in ``drivers.py`` (``"driver"``);
* ``metrics/<metric>.py``: ``read(ctx) -> float | None`` for each per-layer
  metric, where ``<metric>`` is the metric's name up to its first ``.``.

``execute`` runs one cell and returns the result line's object.  It takes
the chip as it finds it; ``run.py`` checks the chip first.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".cache" / "jax"

# JAX monitoring events that mean a program was traced, compiled or loaded
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "compiles",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_loads",
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str) -> dict:
    """The cell's entry, its configuration entry and file, its traffic mix,
    and the end-to-end and per-layer metrics it reports."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]

    def reports(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if reports(m) and m["moves"] in e2e_names]
    return {
        "cell": cell,
        "config": load_json(ROOT / entry["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": e2e,
        "per_layer": layer,
    }


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent stream per use, all drawn from one seed."""
    return np.random.default_rng(np.random.SeedSequence([stream, seed % 2**64]))


def generate_graphs(config: dict, seed: int) -> list:
    """The configuration's graphs, in the sequence a run's cycle visits them.

    A ``graph_seed`` fixes the generator's draw; without one the run's seed
    draws it.  ``orders`` (K) then renames the vertices of that one draw by
    K permutations from fixed streams of ``graph_seed``: every run colors
    the same K graphs, with the same sizes and compiled shapes, in K vertex
    orders.  The run's seed only rotates the sequence: it changes which
    order comes first, not the set."""
    from bench.graphs.csr import relabel

    gen = load_module(BENCH / "graphs" / f"{config['generator']}.py",
                      f"bench_graph_{config['generator']}")
    fixed = config.get("graph_seed")
    g = gen.generate(config["graph"], seed_rng(seed, 0) if fixed is None
                     else np.random.default_rng(fixed))
    k = int(config.get("orders", 0))
    if not k:
        return [g]
    if fixed is None:
        raise ValueError("'orders' needs a 'graph_seed' to fix the orders")
    graphs = [relabel(g, seed_rng(fixed, 2 + i).permutation(g.n))
              for i in range(k)]
    first = seed % k
    log(f"{k} vertex orders a cycle, from order {first}")
    return graphs[first:] + graphs[:first]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept, so only a cell's first run there compiles."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts traces, compiles and cache loads JAX reports, per phase."""

    def __init__(self):
        import jax.monitoring

        self.counts = dict.fromkeys(COMPILE_EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        key = COMPILE_EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


class Run:
    """What a driver gets: the inputs, the program, and the window."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 t_start: float):
        import jax

        sys.path.insert(0, str(ROOT / "src"))
        import repro
        from repro.core.csr import CSRGraph

        self.jax = jax
        self.repro = repro
        self.CSRGraph = CSRGraph
        self.spec = spec
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.compiles = CompileCounter()
        self.graphs = generate_graphs(self.config, seed)
        # the readers' graph: every order has its sizes
        self.graph = self.graphs[0]
        self.t_open = self.t_close = None
        self.window_compiles = None
        self.trace_dir = None
        self.failures = []
        deg = self.graph.degrees
        log(f"graph {self.config['name']}: n={self.graph.n} "
            f"entries={self.graph.entries} max_degree={int(deg.max(initial=0))} "
            f"degree_std={float(deg.std()) if deg.size else 0.0}")

    def annotate(self, name: str):
        return self.jax.profiler.TraceAnnotation(f"bench.{name}")

    def done(self, count: int) -> bool:
        """True once the window should close after ``count`` requests: at
        the end of the first whole cycle over ``graphs`` that ends past
        ``--seconds``, or in a traced run of the first that reaches the
        mix's ``trace_requests``."""
        if count % len(self.graphs):
            return False
        if self.trace:
            return count >= int(self.traffic["trace_requests"])
        return time.perf_counter() - self.t_open >= self.seconds

    def note_failure(self, exc: Exception) -> None:
        if not self.failures:
            log("".join(traceback.format_exception(exc)))
        self.failures.append(repr(exc))
        log(f"request failed: {exc!r}")

    @contextlib.contextmanager
    def window(self):
        """The measured window; under ``--trace 1`` the profiler runs in it."""
        before = self.compiles.snapshot()
        log(f"set-up: programs {before}")
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            # host spans at level 1 keep the benchmark's annotations; the
            # Python tracer would log every call of the host loops
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self.jax.profiler.start_trace(self.trace_dir,
                                          profiler_options=opts)
        self.t_open = time.perf_counter()
        try:
            with self.annotate("window"):
                yield self
        finally:
            self.t_close = time.perf_counter()
            if self.trace:
                self.jax.profiler.stop_trace()
            after = self.compiles.snapshot()
            self.window_compiles = {k: after[k] - before[k] for k in after}

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_start

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_layers(spec: dict, ctx: dict) -> dict:
    """Each per-layer metric its reader finds something for."""
    out = {}
    for m in spec["per_layer"]:
        base, _, suffix = m["name"].partition(".")
        reader = load_module(BENCH / "metrics" / f"{base}.py",
                             f"bench_metric_{base}")
        value = reader.read(dict(ctx, suffix=suffix))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, spec: dict | None = None) -> dict:
    """Run one cell; returns the result line's object."""
    from bench import drivers

    spec = spec or cell_spec(workload)
    run = Run(spec, seed, seconds, trace, t_start)
    out = drivers.DRIVERS[spec["traffic"]["driver"]](run)
    log(f"window: {out['attempted']} requests in {run.window_s} s; programs "
        f"in the window: {run.window_compiles}")
    device = device_info(run.jax)
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    ctx = {"graph": run.graph, "layer": out["layer"], "device": device}
    if trace:
        from bench import profile_reduce as pr

        try:
            summary = pr.read_xspace(pr.find_xspace(run.trace_dir))
        finally:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
        lo, hi = pr.window_of(summary.annotations, "bench.window")
        busy = [pr.busy_ns(d.programs, lo, hi) for d in summary.devices]
        device["busy_s"] = sum(busy) / max(len(busy), 1) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = pr.breakdown(summary, lo, hi)
        ctx.update(trace=summary, window_ns=(lo, hi),
                   busy_s=device["busy_s"], window_s=device["window_s"])
        result["metrics"] = read_layers(spec, ctx)
    else:
        values = {"setup_s": run.setup_s, **out["end_to_end"]}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]}
    # the check runs after the window, the memory reading and the trace
    compared = out["check"]()
    result["correct"] = all(v["value"] <= v["limit"]
                            for v in compared.values())
    result["compared"] = compared
    return result
