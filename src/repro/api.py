"""Unified public coloring API (DESIGN.md §4).

One entry point for every coloring implementation in the repo:

    from repro.api import color
    result = color(g, algorithm="data_driven", heuristic="degree")

Algorithms self-register: each ``core/`` module decorates a small adapter
with ``@register(name)`` at import time, so adding an implementation never
touches this file.  All adapters share the ``ColoringResult`` contract from
``core/coloring.py`` (colors, iterations, work accounting, convergence).

Registered names (see ``algorithms()``):

* ``serial``      — sequential greedy oracle (Alg. 1)
* ``data_driven`` — worklist speculative-greedy, the paper's contribution
* ``fused``       — ``data_driven`` as ONE device program (``lax.while_loop``)
* ``topology``    — work-inefficient all-lanes baseline (Alg. 6)
* ``jp``          — Jones–Plassmann MIS (Alg. 3)
* ``multihash``   — CUSPARSE-csrcolor multi-hash MIS
* ``threestep``   — 3-step GM analogue (device rounds + serial host fix-up)
* ``distance2``   — distance-2 SGR (``repro.d2``; same super-step on G²)
* ``bipartite``   — bipartite partial coloring of a ``BipartiteGraph``
                    column side (the Jacobian-compression workload)
* ``dynamic``     — cold path of the streaming incremental engine
                    (``repro.dynamic``; ``open_session`` is the streaming
                    entry point — mutate with ``apply_delta`` and repair
                    with frontier-sized ``recolor()`` calls, §14)

``color_batch`` colors MANY graphs: for ``algorithm="fused"`` (distance-1)
and ``algorithm="distance2"`` it dispatches to the batched multi-graph
engine (``core/batch.py``) — one jitted call for the whole batch — and
falls back to a per-graph loop otherwise.

Backend selection (§15): ``color(g, backend="pallas")`` routes the rotated
super-step through the fused Pallas kernel (``interpret=True`` off-TPU);
``backend="jax"`` forces the pure-JAX engine, ``backend="auto"`` resolves
to ``jax`` (``kernels/dispatch.py``).  Colors are bit-identical across backends, so the knob
is pure performance policy; engines that cannot host the kernel (the
multi-device sharded engine) fall back to pure-JAX automatically.

Multi-device (§13): ``color(g, engine="sharded")`` runs the sharded ragged
engine over every available device (bit-identical colors, halo-exchange
communication only) and ``color_batch(graphs, engine="sharded")`` places
batches across devices (shard-per-graph when the batch fills the mesh,
partition-within-graph otherwise).  Both fall back to the single-device
engines when only one device is present.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Callable, Iterable

from repro.options import ColorOptions

if TYPE_CHECKING:  # imports stay lazy at runtime to avoid core<->api cycles
    from repro.core.coloring import ColoringResult
    from repro.core.csr import CSRGraph

__all__ = ["register", "color", "color_batch", "algorithms", "get_algorithm",
           "open_session", "ColorOptions"]

_REGISTRY: dict[str, Callable] = {}


def register(name: str):
    """Class-registry decorator: ``@register("jp")`` on a ``(g, **opts)`` adapter."""

    def deco(fn: Callable) -> Callable:
        if name in _REGISTRY and _REGISTRY[name] is not fn:
            raise ValueError(f"algorithm {name!r} registered twice")
        _REGISTRY[name] = fn
        return fn

    return deco


def _ensure_registered() -> None:
    # Importing the packages runs every @register decorator in their modules.
    import repro.core  # noqa: F401
    import repro.d2  # noqa: F401
    import repro.dynamic  # noqa: F401


def open_session(rows, cols=None, *, options: ColorOptions | None = None,
                 **opts):
    """Open a streaming ``ColoringSession`` (lazy alias of ``repro.dynamic``).

    Accepts the unified ``ColorOptions`` object (§19) or the equivalent
    loose kwargs — both normalize identically inside the session.
    """
    from repro.dynamic import open_session as _open_session

    return _open_session(rows, cols, options=options, **opts)


def _normalize(algorithm, options, opts) -> ColorOptions:
    """One ``ColorOptions`` from (positional algorithm | options, kwargs)."""
    if isinstance(algorithm, ColorOptions):
        if options is not None:
            raise TypeError(
                "pass ColorOptions positionally OR as options=, not both")
        options, algorithm = algorithm, None
    return ColorOptions.normalize(options, algorithm=algorithm, **opts)


def algorithms() -> tuple[str, ...]:
    """Sorted names of every registered coloring algorithm."""
    _ensure_registered()
    return tuple(sorted(_REGISTRY))


def get_algorithm(name: str) -> Callable:
    """The registered adapter for ``name`` (raises ValueError if unknown)."""
    _ensure_registered()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {name!r}; registered: {', '.join(sorted(_REGISTRY))}"
        ) from None


def color(graph: "CSRGraph", algorithm: "str | ColorOptions | None" = None,
          *, options: ColorOptions | None = None, **opts) -> "ColoringResult":
    """Color ``graph`` with the named algorithm; extra ``opts`` pass through.

    Returns a ``ColoringResult``; ``result.colors`` is an int32 array in
    ``[1, num_colors]`` and ``result.num_colors`` the color count.

    Options come in either spelling (§19) — a frozen ``ColorOptions``
    (positionally in place of ``algorithm``, or as ``options=``) or loose
    kwargs; both normalize into the same object first, so results are
    bit-identical across spellings.  Kwargs override fields already set on
    the options object.  The default algorithm is ``"data_driven"``.

    Robustness knobs (DESIGN.md §17):

    ``validate_input`` runs the ``repro.ingest.sanitize_csr`` front door on
    a ``CSRGraph`` input first — ``"strict"`` raises ``IngestError`` with a
    structured report on any defect (asymmetry, self-loops, duplicates,
    unsorted rows, bad indices, broken indptr), ``"repair"`` fixes the
    input and records every action on ``result.degradations``.

    ``ensure_valid=True`` guarantees the returned coloring validates
    against the algorithm's conflict relation: a run that failed to
    converge (or returned corrupt colors) is escalated through the §17
    guarantee ladder — deterministic reseed → full iteration budget →
    serialize-the-survivors → serial oracle — instead of surfacing an
    error.  Every escalation taken is recorded in
    ``result.degradations`` and emitted as ``guarantee_ladder`` obs spans.
    """
    from repro.obs.spans import span

    o = _normalize(algorithm, options, opts)
    algorithm = o.algorithm or "data_driven"
    fn = get_algorithm(algorithm)
    # the request's root span: every phase span of the call nests under it
    with span("color", algorithm=algorithm, n=getattr(graph, "n", None)):
        return _color(graph, algorithm, fn, o)


def _color(graph, algorithm: str, fn: Callable, o: ColorOptions):
    engine_opts = o.engine_kwargs()
    pre = ()
    if o.validate_input is not None:
        from repro.core.csr import CSRGraph as _CSR
        from repro.ingest import sanitize_csr

        if not isinstance(graph, _CSR):
            raise TypeError(
                "validate_input= applies to CSRGraph inputs; got "
                f"{type(graph).__name__} (sanitize bipartite halves with "
                "sanitize_csr(..., require_symmetric=False) directly)")
        graph, report = sanitize_csr(graph, policy=o.validate_input)
        pre = report.degradations()
    result = fn(graph, **engine_opts)
    if pre:
        result.degradations = pre + tuple(result.degradations)
    if o.ensure_valid:
        result = _apply_ladder(graph, algorithm, fn, engine_opts, result)
    return result


def _apply_ladder(graph, algorithm: str, fn: Callable, opts: dict, result):
    """Escalate ``result`` through the §17 guarantee ladder (see above)."""
    from repro.core.guarantee import ensure_valid_result, square_graph
    from repro.obs.spans import SpanRecorder

    if algorithm == "bipartite":
        cg = graph.column_conflict_graph()
    elif algorithm == "distance2":
        cg = square_graph(graph)
    else:
        cg = graph

    def rerun(rung):
        o = dict(opts)
        if rung == "reseed":
            cur = o.get("heuristic", "degree")
            o["heuristic"] = "id" if cur == "degree" else "degree"
        elif rung == "budget_extension":
            o["max_iters"] = None  # the engine default: always enough
            if o.get("tail_serial", "auto") is None:
                o["tail_serial"] = "auto"
        return fn(graph, **o)

    if result.trace is not None:
        # §16 surfacing: ladder spans land on the run's own trace even
        # without a user recorder (an outer recorder still sees them)
        with SpanRecorder() as rec:
            out = ensure_valid_result(cg, result, rerun)
        if out.trace is not None and rec.events:
            out.trace.spans = list(out.trace.spans or []) + rec.events
        return out
    return ensure_valid_result(cg, result, rerun)


# the knobs the batched fused engine understands — everything else must go
# through the per-graph ``color`` path.  Derived from ColorOptions fields
# (this replaced the old hand-rolled ``supported = {...}`` set; §19).
_BATCH_SUPPORTED = ("heuristic", "firstfit", "max_iters", "tail_serial",
                    "engine", "devices", "backend", "trace",
                    "validate_input", "ensure_valid")


def color_batch(
    graphs: Iterable["CSRGraph"],
    algorithm: "str | ColorOptions | None" = None, *,
    options: ColorOptions | None = None, **opts
) -> "list[ColoringResult]":
    """Color many graphs; the serving-path entry point.

    Options come as a ``ColorOptions`` or loose kwargs, exactly like
    ``color`` (§19); results are bit-identical across spellings.  The
    default algorithm is ``"fused"``.

    ``trace=True`` (supported by every algorithm here) attaches a per-run
    ``RunTrace`` to each result — see ``repro.obs``.

    ``algorithm="fused"`` uses the batched engine: the graphs are packed into
    one stacked padded-adjacency layout and a single jitted ``while_loop``
    colors all of them concurrently (see ``core/batch.py``).  Any other name
    loops ``color`` over the graphs.  Algorithm-specific knobs the batched
    engine cannot honor are refused by name with the supported list.
    """
    graphs = list(graphs)
    o = _normalize(algorithm, options, opts)
    algorithm = o.algorithm or "fused"
    if algorithm in ("fused", "distance2"):
        from repro.core.batch import color_batch_fused, color_batch_sharded

        extra = o.extra_dict()
        devices = extra.pop("devices", None)
        if extra:
            raise ValueError(
                f"options {sorted(extra)} are not supported by the batched "
                f"fused engine (supported: {sorted(_BATCH_SUPPORTED)}); "
                f"use color(g, {algorithm!r}, ...) per graph instead"
            )
        pre = [()] * len(graphs)
        if o.validate_input is not None:
            from repro.ingest import sanitize_csr

            sanitized = []
            for i, g in enumerate(graphs):
                g, report = sanitize_csr(g, policy=o.validate_input)
                sanitized.append(g)
                pre[i] = report.degradations()
            graphs = sanitized
        kw = o.engine_kwargs()
        kw.pop("engine", None)
        engine = o.engine or "batch"
        if engine == "sharded":
            results = color_batch_sharded(
                graphs, distance2=(algorithm == "distance2"),
                devices=devices, **kw
            )
        elif engine != "batch":
            raise ValueError(
                f"unknown batch engine {engine!r}; options: batch, sharded"
            )
        elif devices is not None:
            raise ValueError(
                "devices= only applies to engine='sharded'; the default "
                "batched engine runs on the default device placement"
            )
        else:
            results = color_batch_fused(
                graphs, distance2=(algorithm == "distance2"), **kw
            )
        for g, r, p in zip(graphs, results, pre):
            if p:
                r.degradations = tuple(p) + tuple(r.degradations)
        if o.ensure_valid:
            fn = get_algorithm(algorithm)
            results = [_apply_ladder(g, algorithm, fn, kw, r)
                       for g, r in zip(graphs, results)]
        return results
    per_graph = o.merged(algorithm=algorithm)
    return [color(g, options=per_graph) for g in graphs]
