"""Batched multi-graph SGR engine — one device program colors B graphs.

The serving-scale generalization of ``coloring.py``'s ``fused`` mode
(DESIGN.md §4).  ``fused`` proved the whole coloring of ONE graph runs as a
single jitted ``lax.while_loop``; here the same super-step is lifted over a
leading batch axis with ``jax.vmap`` so a single dispatch colors a *batch*
of heterogeneous graphs concurrently — amortizing launch overhead across
requests the way Rokos/Bogle amortize it across subdomains.

Layout (``GraphBatch``): B CSR graphs pack into one stacked padded-adjacency
tensor ``(B, n_max, W)``.  Every graph shares the sentinel ``n_max`` (its
per-graph sentinel ``n_i`` is remapped at pack time), so the ``colors_ext``
trick from ``core/csr.py`` carries over per batch row: ``colors_ext`` is
``(B, n_max + 1)`` with slot ``n_max`` pinned to color 0, making both the
padding lanes inside a row and the all-sentinel padding *rows* of smaller
graphs inert.  Worklists are ``(B, n_max)`` with sentinel fill; a finished
graph's row compacts to all-sentinel and its lanes become no-ops.

Since §12 the batched super-step is the ROTATED one (one gather serves
conflict detection and FirstFit) and the per-graph adaptive
tail-serialization carries over: a graph whose worklist drops to its tail
threshold — or stalls — FREEZES (its lanes turn sentinel) while the others
keep speculating; when every graph is frozen or done, one vmapped serial
tail pass finishes all of them.  Each graph therefore sees exactly the
schedule the per-graph fused driver would give it, so the batched result is
bit-identical to per-graph ``mode="fused"`` runs whenever those resolve to a
single degree class (always true below the auto-tiling size gate) — tested
in ``tests/test_batch.py``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.coloring import (
    ColoringResult,
    DenseRows,
    _packed_gather_ok,
    _stalled,
    order_tail,
    ragged_superstep,
    resolve_tail_threshold,
    serial_tail_step,
    sgr_step,
)
from repro.core.csr import CSRGraph, next_pow2
from repro.obs.spans import SpanRecorder, span
from repro.obs.trace import (
    assemble_trace,
    empty_trace,
    resolve_trace_cap,
    ring_rows,
)

__all__ = ["GraphBatch", "SessionBatch", "batched_sgr_step",
           "batched_ragged_step", "color_batch_fused", "color_batch_sharded",
           "open_session_batch", "session_shape_class"]


@dataclasses.dataclass(frozen=True)
class GraphBatch:
    """B CSR graphs packed into one stacked padded-adjacency layout."""

    adj: jax.Array            # (B, n_max, W) int32; sentinel n_max in padding
    deg_ext: jax.Array        # (B, n_max + 1) int32; sentinel slot holds 0
    sizes: tuple[int, ...]    # per-graph vertex counts n_i
    n_max: int
    distance2: bool = False   # True when adj holds the SQUARE adjacencies

    @property
    def B(self) -> int:
        return len(self.sizes)

    @property
    def width(self) -> int:
        return int(self.adj.shape[2])

    @classmethod
    def from_graphs(
        cls,
        graphs: Sequence[CSRGraph],
        width: int | None = None,
        distance2: bool = False,
        validate_input: str | None = None,
    ) -> "GraphBatch":
        """Pack ``graphs``; ``width`` may widen (never narrow) the adjacency.

        ``distance2=True`` packs each graph's SQUARE adjacency (G², two-hop
        neighborhoods) while keeping the ORIGINAL degrees for the conflict
        loser rule — the same convention as ``repro.d2.color_distance2``'s
        precomputed strategy, so batched D2 stays bit-identical to per-graph
        fused D2 runs (DESIGN.md §11).

        ``validate_input="strict"|"repair"`` runs every member through the
        §17 ingest front door before packing (padded rows silently absorb a
        malformed CSR — an unsorted or duplicated row packs into garbage
        adjacency slots without erroring, so the batch is where validation
        pays off most).
        """
        graphs = list(graphs)
        if validate_input is not None:
            from repro.ingest import sanitize_csr

            graphs = [
                sanitize_csr(g, policy=validate_input)[0] for g in graphs
            ]
        sizes = tuple(g.n for g in graphs)
        n_max = max(sizes, default=0)
        adj_graphs = [g.square() for g in graphs] if distance2 else graphs
        need = max((g.max_degree for g in adj_graphs), default=0)
        W = max(need, width or 0, 1)
        adj = np.full((len(graphs), n_max, W), n_max, dtype=np.int32)
        deg = np.zeros((len(graphs), n_max + 1), dtype=np.int32)
        for b, (g, ag) in enumerate(zip(graphs, adj_graphs)):
            if g.n == 0:
                continue
            a = ag.padded_adjacency(W)
            adj[b, : g.n] = np.where(a == g.n, n_max, a)  # shared sentinel
            deg[b, : g.n] = g.degrees
        return cls(jnp.asarray(adj), jnp.asarray(deg), sizes, n_max, distance2)


@partial(
    jax.jit,
    static_argnames=("heuristic", "kind", "coarsen_ff", "coarsen_cr",
                     "use_kernel"),
)
def batched_sgr_step(
    adj,
    deg_ext,
    colors_ext,
    wl,
    *,
    heuristic: str = "degree",
    kind: str = "bitset",
    coarsen_ff: int = 1,
    coarsen_cr: int = 1,
    use_kernel: bool = False,
):
    """Classic ``sgr_step`` over a leading batch axis: (B, …) in, (B, …) out."""
    step = partial(
        sgr_step,
        heuristic=heuristic,
        kind=kind,
        coarsen_ff=coarsen_ff,
        coarsen_cr=coarsen_cr,
        use_kernel=use_kernel,
    )
    return jax.vmap(step)(adj, deg_ext, colors_ext, wl)


def _graph_ragged_step(adj, deg_ext, colors_ext, wl, *, heuristic, kind,
                       use_kernel, pack_degrees=False):
    """One graph's rotated super-step over its dense packed adjacency."""
    return ragged_superstep(
        DenseRows(adj).rows, deg_ext, colors_ext, wl,
        heuristic=heuristic, kind=kind, use_kernel=use_kernel,
        pack_degrees=pack_degrees,
    )


@partial(jax.jit, static_argnames=("heuristic", "kind", "use_kernel",
                                   "pack_degrees"))
def batched_ragged_step(adj, deg_ext, colors_ext, wl, *,
                        heuristic: str = "degree", kind: str = "bitset",
                        use_kernel: bool = False, pack_degrees: bool = False):
    """Rotated super-step over a leading batch axis (§12)."""
    step = partial(_graph_ragged_step, heuristic=heuristic, kind=kind,
                   use_kernel=use_kernel, pack_degrees=pack_degrees)
    return jax.vmap(step)(adj, deg_ext, colors_ext, wl)


@partial(jax.jit, static_argnames=("heuristic", "kind", "use_kernel",
                                   "tail_enabled", "pack_degrees",
                                   "trace_cap"))
def _run_batch(adj, deg_ext, sizes, thrs, max_iters, *, heuristic, kind,
               use_kernel, tail_enabled, pack_degrees=False, trace_cap=0):
    """Speculative phase: per-graph freeze on threshold/stall (§12).

    ``trace_cap`` (§16, static) threads a ``(cap, B, 3)`` ring through the
    carry recording ``[live_in, live_out, max_color]`` per graph per global
    step (``live_in = -1`` marks a frozen/finished graph); ``trace_cap=0``
    compiles the identical pre-§16 program.
    """
    B, n_max, _ = adj.shape
    ids = jnp.arange(n_max, dtype=jnp.int32)
    in_graph = ids[None, :] < sizes[:, None]
    wl0 = jnp.where(in_graph, ids[None, :], n_max)
    # bootstrap identity: every real vertex takes color 1 (see coloring.py)
    colors0 = jnp.concatenate(
        [jnp.where(in_graph, 1, 0), jnp.zeros((B, 1), jnp.int32)], axis=1
    ).astype(jnp.int32)
    counts0 = sizes.astype(jnp.int32)
    iters0 = (sizes > 0).astype(jnp.int32)
    zeros = jnp.zeros((B,), dtype=jnp.int32)
    active0 = counts0 > (thrs if tail_enabled else 0)

    def cond(state):
        return jnp.any(state[4]) & (state[7] < max_iters)

    def body(state):
        colors_ext, wl, counts, prev, active, iters_b, work_b, it = state[:8]
        wl_in = jnp.where(active[:, None], wl, n_max)
        colors_ext, wl_new, cnt_new = batched_ragged_step(
            adj, deg_ext, colors_ext, wl_in,
            heuristic=heuristic, kind=kind, use_kernel=use_kernel,
            pack_degrees=pack_degrees,
        )
        new_counts = jnp.where(active, cnt_new, counts)
        new_prev = jnp.where(active, counts, prev)
        wl = jnp.where(active[:, None], wl_new, wl)
        iters_b = iters_b + active.astype(jnp.int32)
        work_b = work_b + jnp.where(active, cnt_new, 0)
        out = (colors_ext, wl, new_counts, new_prev, active, iters_b,
               work_b, it + 1)
        if trace_cap:
            row = jnp.stack(
                [jnp.where(active, counts, -1),
                 jnp.where(active, cnt_new, -1),
                 jnp.max(colors_ext[:, :-1], axis=1)], axis=-1,
            ).astype(jnp.int32)
            idx = lax.rem(it - 1, jnp.int32(trace_cap))
            out = out + (state[8].at[idx].set(row),)
        still = active & (new_counts > 0) & (it + 1 < max_iters)
        if tail_enabled:
            still &= (new_counts > thrs) & ~_stalled(iters_b, new_counts,
                                                     new_prev)
        return out[:4] + (still,) + out[5:]

    state = (colors0, wl0, counts0, counts0, active0, iters0, zeros,
             jnp.int32(1))
    if trace_cap:
        state = state + (jnp.zeros((trace_cap, B, 3), jnp.int32),)
    return lax.while_loop(cond, body, state)


@partial(jax.jit, static_argnames=("kind",))
def _run_batch_tail(adj, deg_ext, colors_ext, wl, run_tail, stalled, sizes, *,
                    kind):
    """Vmapped serial tail: one sequential pass finishes every live graph.

    Stalled graphs discard their speculative colors and serialize ALL their
    vertices (largest-degree-first); threshold-frozen graphs serialize just
    their remaining worklists — exactly what the per-graph driver does.
    """
    B, n_max, _ = adj.shape
    ids = jnp.arange(n_max, dtype=jnp.int32)
    full_wl = jnp.where(ids[None, :] < sizes[:, None], ids[None, :], n_max)
    wl = jnp.where(stalled[:, None], full_wl, wl)
    ordered = jax.vmap(order_tail)(wl, deg_ext)
    wl_in = jnp.where(run_tail[:, None], ordered, n_max)

    def tail_one(adj_b, colors_b, wl_b):
        return serial_tail_step(DenseRows(adj_b).row1, colors_b, wl_b, kind)

    return jax.vmap(tail_one)(adj, colors_ext, wl_in)


def color_batch_fused(
    graphs: "Iterable[CSRGraph] | GraphBatch",
    *,
    heuristic: str = "degree",
    firstfit: str = "bitset",
    use_kernel: bool = False,
    max_iters: int | None = None,
    distance2: bool = False,
    tail_serial="auto",
    backend: str | None = None,
    trace=False,
) -> list[ColoringResult]:
    """Color B graphs in ONE jitted batched ``while_loop``; one result each.

    ``trace=True`` (§16) attaches a per-graph ``RunTrace`` to every result,
    assembled from one shared on-device ring over the batched loop — each
    graph's rows cover exactly the global steps it was live in, so frozen
    capacity steps (charged to ``padded_work``) do NOT appear in its
    ``cells`` series (the trace sum is a lower bound there).

    ``backend="pallas"`` routes the vmapped rotated super-step through the
    fused Pallas kernel (§15; the kernel vmaps over the batch axis in both
    compiled and interpret mode) — colors are bit-identical to
    ``backend="jax"``.

    The speculative loop runs until every graph converges, freezes at its
    tail threshold, or stalls; frozen graphs idle as all-sentinel no-op rows
    (their reported ``iterations`` count only live super-steps).  One
    vmapped ``serial_tail_step`` then finishes all frozen worklists at once.
    ``padded_work`` charges every graph the full ``n_max × W`` gather cells
    per global step — the capacity cost of batching — while ``work_items``
    counts its genuinely live worklist entries.

    ``distance2=True`` is the batched D2 path: the packed adjacency is each
    graph's square (see ``GraphBatch.from_graphs``), everything downstream
    is unchanged, and results are bit-identical to per-graph
    ``color_distance2(mode="fused", strategy="precomputed")`` runs.
    """
    from repro.kernels.dispatch import resolve_backend

    # resolve once; recursion below passes the resolved knob (idempotent:
    # resolve_backend(None, use_kernel=True) -> "pallas").  The batch's
    # dense stacked layout has no per-graph CSR arrays, so pallas-csr
    # degrades to the gathered kernel (bit-identical, §18)
    use_kernel = resolve_backend(backend, use_kernel) in (
        "pallas", "pallas-csr")
    if isinstance(graphs, GraphBatch):
        if graphs.distance2 != distance2:
            raise ValueError(
                f"GraphBatch was packed with distance2={graphs.distance2} but "
                f"color_batch_fused was called with distance2={distance2}; "
                f"re-pack with GraphBatch.from_graphs(graphs, distance2=...)"
            )
        batch = graphs
    else:
        # Width-bucketed sub-batches (batch-level Merrill load balancing,
        # §12): one skewed graph would otherwise force its Δmax padding onto
        # every row of the stacked tensor.  Results are per-graph independent
        # (each graph sees exactly its per-graph fused schedule), so grouping
        # is a pure perf policy — colors are identical either way.  Callers
        # who pre-packed a GraphBatch keep their own layout.
        graphs = list(graphs)
        keys = [
            next_pow2(max(
                g.two_hop_degree_bound() if distance2 else g.max_degree, 1))
            for g in graphs
        ]
        if len(set(keys)) > 1:
            by_key: dict[int, list[int]] = {}
            for i, k in enumerate(keys):
                by_key.setdefault(k, []).append(i)
            results: list = [None] * len(graphs)
            for idxs in by_key.values():
                sub = color_batch_fused(
                    GraphBatch.from_graphs([graphs[i] for i in idxs],
                                           distance2=distance2),
                    heuristic=heuristic, firstfit=firstfit,
                    backend=("pallas" if use_kernel else "jax"),
                    max_iters=max_iters,
                    distance2=distance2, tail_serial=tail_serial,
                    trace=trace,
                )
                for i, r in zip(idxs, sub):
                    results[i] = r
            return results
        batch = GraphBatch.from_graphs(graphs, distance2=distance2)
    algo = "batched_fused_sgr_d2" if distance2 else "batched_fused_sgr"
    if batch.B == 0:
        return []
    if batch.n_max == 0:
        out = [ColoringResult(np.zeros(0, np.int32), 0, 0, 0, True, algo)
               for _ in range(batch.B)]
        if trace:
            for r in out:
                r.trace = empty_trace(algo)
        return out
    max_iters = max_iters or batch.n_max + 1
    trace_cap = resolve_trace_cap(trace, max_iters)

    def run():
        sizes = jnp.asarray(np.asarray(batch.sizes, dtype=np.int32))
        tail_enabled, _ = resolve_tail_threshold(tail_serial, batch.n_max)
        thrs_np = np.asarray(
            [resolve_tail_threshold(tail_serial, n)[1] for n in batch.sizes],
            dtype=np.int32,
        )
        pack = _packed_gather_ok(batch.width)
        with span("superstep_loop", mode="batched", B=batch.B):
            state = _run_batch(
                batch.adj, batch.deg_ext, sizes, jnp.asarray(thrs_np),
                jnp.int32(max_iters),
                heuristic=heuristic, kind=firstfit, use_kernel=use_kernel,
                tail_enabled=tail_enabled,
                # degrees <= packed width, colors <= width + 1 (coloring.py)
                pack_degrees=pack, trace_cap=trace_cap,
            )
        colors_ext, wl, counts, prev, _, iters_b, work_b, it = state[:8]
        counts = np.asarray(counts)
        prev = np.asarray(prev)
        iters_b = np.asarray(iters_b).copy()
        work_b = np.asarray(work_b).copy()
        steps = int(it) - 1
        ordered = ring_rows(np.asarray(state[8]), steps) if trace_cap else None
        sizes_np = np.asarray(batch.sizes, dtype=np.int32)
        run_tail = tail_enabled & (counts > 0) & (iters_b < max_iters)
        stalled = (run_tail & (counts > thrs_np)
                   & _stalled(iters_b, counts, prev))
        counts_pre = counts.copy()
        if run_tail.any():
            with span("serial_tail", live=int(counts[run_tail].sum())):
                colors_ext = _run_batch_tail(
                    batch.adj, batch.deg_ext, colors_ext, wl,
                    jnp.asarray(run_tail), jnp.asarray(stalled),
                    jnp.asarray(sizes_np), kind=firstfit,
                )
                colors_ext.block_until_ready()  # the readback waits anyway
            iters_b += run_tail
            work_b += np.where(stalled, sizes_np,
                               np.where(run_tail, counts, 0))
            counts = np.where(run_tail, 0, counts)
        colors = np.asarray(colors_ext[:, : batch.n_max])
        cells = batch.n_max * batch.width
        out = []
        for b, n in enumerate(batch.sizes):
            # the bootstrap step processes all n vertices; work_b accumulates
            # the live counts of every later step (mirrors the fused driver)
            res = ColoringResult(
                colors[b, :n].copy(),
                int(iters_b[b]),
                int(work_b[b]) + n if n else 0,
                steps * cells + (cells if run_tail[b] else 0),
                converged=int(counts[b]) == 0,
                algorithm=algo,
            )
            if trace_cap:
                # per-graph rows from the shared (cap, B, 3) ring; a graph's
                # live steps are a PREFIX of the global steps, so its kept
                # rows stay contiguous — drop the boot row whenever the ring
                # overwrote any of its early live steps
                spec = [(int(r[b, 0]), int(r[b, 1]), int(r[b, 2]))
                        for r in ordered if int(r[b, 0]) >= 0]
                k_b = int(iters_b[b]) - (1 if n else 0) - int(run_tail[b])
                rows_b = ([(n, 0, n, 1, 0, 0, 0, 0)]
                          if n and len(spec) == k_b else [])
                rows_b += [(li, li - lo, lo, mc, cells, 0, 0, 0)
                           for li, lo, mc in spec]
                if run_tail[b]:
                    rows_b.append((int(counts_pre[b]), int(counts_pre[b]), 0,
                                   int(colors[b, :n].max(initial=0)),
                                   cells, 1, 0, 0))
                res.trace = assemble_trace(rows_b, int(iters_b[b]),
                                           trace_cap, algo)
            out.append(res)
        return out

    if not trace:
        return run()
    with SpanRecorder() as rec:
        out = run()
    for r in out:
        if r.trace is not None:
            r.trace.spans = rec.events
    return out


def session_shape_class(session) -> tuple:
    """The pow2 shape class a session's recolor dispatch buckets under.

    ``(pow2 n, pow2 max_degree)`` — the two quantities that dominate a
    frontier recolor's jit cache key (§14: padded DeviceCSR width and
    worklist/class shapes both derive from them).  Two sessions in the
    same class fed similar-size frontiers present REPEATING keys to the
    jitted engine, so the serving layer's micro-batcher (§19) keys its
    buckets on ``(session_shape_class(s), ColorOptions)``: the first
    request of a bucket compiles, the rest of the bucket reuses.
    """
    g = session.delta.graph()
    return (next_pow2(max(session.n, 1)),
            next_pow2(max(g.max_degree, 1)))


class SessionBatch:
    """Per-graph ``ColoringSession``s for B-graph churn (§14 serving path).

    The streaming analogue of ``color_batch_fused``: B user graphs are held
    open as persistent sessions, mutations arrive per graph
    (``apply_delta(b, ...)``), and one ``recolor()`` sweep repairs exactly
    the sessions whose graphs are dirty — clean graphs return their
    committed coloring as a zero-work no-op, so a sweep's total work is
    proportional to the *churned* frontier across the batch, not to
    ``Σ n_i``.  Sessions are independent (their frontiers never interact),
    so per-graph recoloring is exact, and each graph's colors match what a
    standalone ``ColoringSession`` fed the same deltas would hold.

    Dispatch is BUCKETED (§19): a ``recolor()`` sweep orders the dirty
    sessions by pow2 shape class (``session_shape_class``) so same-class
    sessions run consecutively and share the jitted engine's cache
    entries — per-graph results still come back in graph order, and the
    order sessions run in cannot change any colors (independence above).

    Accepts the unified ``ColorOptions`` (``options=``) or the equivalent
    loose session kwargs, like ``open_session`` (§19).
    """

    def __init__(self, graphs: "Iterable[CSRGraph]", *, options=None,
                 **opts):
        from repro.dynamic import ColoringSession  # lazy: dynamic -> core

        if options is not None or opts:
            from repro.options import ColorOptions

            opts = ColorOptions.normalize(options, **opts).session_kwargs()
        self.sessions = [ColoringSession(g, **opts) for g in graphs]

    @property
    def B(self) -> int:
        return len(self.sessions)

    def apply_delta(self, b: int, **delta) -> np.ndarray:
        """Mutate graph ``b``; returns the ids it dirtied (see ColoringSession)."""
        return self.sessions[b].apply_delta(**delta)

    def dirty(self) -> list[int]:
        """Indices of graphs with a pending (non-empty) frontier."""
        return [b for b, s in enumerate(self.sessions) if s.frontier().size]

    def buckets(self) -> "dict[tuple, list[int]]":
        """Dirty graph indices grouped by pow2 shape class (dispatch order)."""
        out: dict[tuple, list[int]] = {}
        for b, s in enumerate(self.sessions):
            if s.pending_dirty:
                out.setdefault(session_shape_class(s), []).append(b)
        return out

    def recolor(self, *, full: bool = False) -> list[ColoringResult]:
        """Repair every dirty session; one (possibly no-op) result per graph.

        Dirty sessions run bucket-by-bucket (see class doc); clean ones
        no-op afterwards.  Results come back in graph order regardless.
        """
        results: list = [None] * self.B
        for _, idxs in sorted(self.buckets().items()):
            for b in idxs:
                results[b] = self.sessions[b].recolor(full=full)
        for b, s in enumerate(self.sessions):
            if results[b] is None:
                results[b] = s.recolor(full=full)
        return results

    def results(self) -> list[ColoringResult]:
        return [s.result for s in self.sessions]

    def validate(self) -> bool:
        return all(s.validate() for s in self.sessions)

    def metrics(self) -> dict:
        """Aggregated engine-cache accounting, per shape-class bucket."""
        per_bucket: dict = {}
        hits = misses = 0
        for s in self.sessions:
            m = s.metrics()
            key = repr(session_shape_class(s))
            agg = per_bucket.setdefault(
                key, {"sessions": 0, "engine_cache_hits": 0,
                      "engine_cache_misses": 0})
            agg["sessions"] += 1
            agg["engine_cache_hits"] += m["engine_cache_hits"]
            agg["engine_cache_misses"] += m["engine_cache_misses"]
            hits += m["engine_cache_hits"]
            misses += m["engine_cache_misses"]
        return {"engine_cache_hits": hits, "engine_cache_misses": misses,
                "buckets": per_bucket}


def open_session_batch(graphs: "Iterable[CSRGraph]", *, options=None,
                       **opts) -> SessionBatch:
    """Open per-graph streaming sessions over ``graphs`` (§14 churn serving)."""
    return SessionBatch(graphs, options=options, **opts)


_EMPTY = CSRGraph(np.zeros(1, np.int64), np.zeros(0, np.int32))


def color_batch_sharded(
    graphs: "Iterable[CSRGraph]",
    *,
    devices=None,
    heuristic: str = "degree",
    firstfit: str = "bitset",
    use_kernel: bool = False,
    max_iters: int | None = None,
    distance2: bool = False,
    tail_serial="auto",
    backend: str | None = None,
    trace=False,
) -> list[ColoringResult]:
    """Place a multi-graph batch across devices (§13 batch placement).

    Two regimes, both bit-identical to the single-device batched engine
    (which is itself bit-identical to per-graph ``mode="fused"`` runs):

    * ``B >= ndev`` — **shard-per-graph**: the usual width-bucketed
      sub-batches, with each sub-batch's stacked tensors sharded on the
      BATCH axis (padded to a device multiple with empty no-op graphs).
      Graphs are independent, so the partitioned program needs no
      cross-device communication at all — placement is a pure perf policy.
    * ``B < ndev`` — **partition-within-graph**: too few graphs to fill the
      mesh, so each one runs the single-graph sharded engine (§13 halo
      exchange) over all devices in turn.
    """
    devices = list(devices) if devices is not None else jax.devices()
    ndev = len(devices)
    graphs = list(graphs)
    B = len(graphs)
    opts = dict(heuristic=heuristic, firstfit=firstfit,
                max_iters=max_iters, tail_serial=tail_serial, trace=trace)
    if ndev <= 1 or B == 0:
        return color_batch_fused(graphs, distance2=distance2,
                                 use_kernel=use_kernel, backend=backend,
                                 **opts)
    if use_kernel:
        raise ValueError("sharded batch placement does not support "
                         "use_kernel=True")
    from repro.kernels.dispatch import resolve_backend

    # §15 fallback: multi-device placement runs pure-JAX regardless of a
    # pallas request (bit-identical colors); validate the name regardless
    resolve_backend(backend)
    if B < ndev:
        if distance2:
            from repro.d2.coloring import color_distance2

            return [color_distance2(g, engine="sharded", devices=devices,
                                    backend=backend, **opts) for g in graphs]
        from repro.core.coloring import color_data_driven

        return [color_data_driven(g, engine="sharded", devices=devices,
                                  backend=backend, **opts) for g in graphs]

    mesh = Mesh(np.asarray(devices), ("b",))
    sh3 = NamedSharding(mesh, P("b", None, None))
    sh2 = NamedSharding(mesh, P("b", None))
    keys = [
        next_pow2(max(
            g.two_hop_degree_bound() if distance2 else g.max_degree, 1))
        for g in graphs
    ]
    by_key: dict[int, list[int]] = {}
    for i, k in enumerate(keys):
        by_key.setdefault(k, []).append(i)
    results: list = [None] * B
    for idxs in by_key.values():
        sub = [graphs[i] for i in idxs]
        sub += [_EMPTY] * ((-len(sub)) % ndev)  # no-op rows to a device multiple
        batch = GraphBatch.from_graphs(sub, distance2=distance2)
        batch = dataclasses.replace(
            batch,
            adj=jax.device_put(batch.adj, sh3),
            deg_ext=jax.device_put(batch.deg_ext, sh2),
        )
        res = color_batch_fused(batch, distance2=distance2,
                                use_kernel=use_kernel, **opts)
        for i, r in zip(idxs, res):
            results[i] = r
    return results
