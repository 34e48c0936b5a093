"""Sharded ragged coloring engine — the §12 super-step at pod scale (§13).

Multi-device coloring as a first-class engine on the rotated fused
super-step.  A ``PartitionedCSR`` plan (``core/csr.py``) assigns each device
a degree-balanced contiguous vertex range and splits it into *interior*
vertices (whose colors are never read off-device) and *boundary* vertices
(the halo send list), computed once at partition time.  Each super-step is
then one ``shard_map`` program over a 1-D mesh:

* **halo exchange** — every device contributes the colors of its
  (boundary ∩ previous-worklist) vertices: after the materialized bootstrap,
  a color can only change when its vertex is on the worklist, so that set
  covers every remote read that could have gone stale.  One ``all_gather``
  of ``(id, color)`` pairs replaces the pre-§13 engine's TWO full-array
  all-gathers, interior vertices never communicate, and the payload shrinks
  with the worklist.
* **rotated fused super-step per shard** — the unchanged
  ``ragged_superstep`` (one adjacency + one neighbor-color gather serving
  both ConflictResolve and FirstFit, packed color|deg<<16 single-gather
  mode) with degree-tiled dispatch over global log-spaced classes.  Every
  shard speculates against the same exchanged snapshot and writes are
  disjoint, so a sharded step is bit-identical to the single-device tiled
  step by the §12 tiled ≡ untiled argument — sharded colors equal ragged
  colors exactly, on every graph.
* **coordinated adaptive tail** — live counts reduce globally on the host
  loop; when the total hits the tail threshold (or the worklist stalls) the
  survivors are gathered to one device and finished with the same ordered
  serial FirstFit pass the single-device engine uses (LDF; stall tails
  discard the failed speculation and re-greedy the whole graph), then the
  result is scattered back by range assembly.

Work accounting mirrors the fused driver (post-step live totals + the
materialized bootstrap; ``padded_work`` = dispatched lanes × tile width),
so with one device the engine reproduces ``color_data_driven(mode="fused")``
bit-for-bit *including* the accounting — the regression anchor in
``tests/test_sharded.py``.  ``ColoringResult.halo_bytes_per_step`` reports
the received halo bytes per device per super-step averaged over the run
(ids + colors, padded lanes included), the number to compare against the
pre-§13 engine's ``2 × 4 × n`` per step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.coloring import (
    ColoringResult,
    _graph_device_cache,
    _packed_gather_ok,
    _resolve_classes,
    _stalled,
    compact,
    order_tail,
    provider_tail,
    ragged_superstep,
    resolve_tail_threshold,
)
from repro.core.csr import CSRGraph, DeviceCSR, PartitionedCSR, next_pow2
from repro.core.heuristics import HEURISTICS
from repro.obs.spans import SpanRecorder, span
from repro.obs.trace import assemble_trace, empty_trace, resolve_trace_cap

__all__ = ["ShardRows", "color_distributed", "run_sharded_engine"]


class ShardRows:
    """Per-shard CSR row provider over GLOBAL vertex ids (§13).

    The ``DeviceCSR`` two-level gather rebased to a contiguous range: the
    shard holds its own rows' R/C slices (column ids stay global, so
    gathered tiles index the globally-addressed color view) and maps a
    global worklist id to its local row as ``id - start``.  Ids outside the
    shard — only the sentinel ``n`` in practice — read all-sentinel rows.
    """

    def __init__(self, row_starts, col_padded, deg_loc, start, n: int,
                 n_loc: int, max_width: int):
        self.row_starts = row_starts    # (L+1,) int32 local offsets
        self.col_padded = col_padded    # (Mcap,) int32 GLOBAL column ids
        self.deg_loc = deg_loc          # (L+1,) int32 local degrees
        self.start = start              # scalar: first owned global id
        self.n = int(n)
        self.n_loc = int(n_loc)
        self.max_width = int(max_width)

    def rows(self, ids, width: int | None = None):
        width = self.max_width if width is None else int(width)
        lidx = ids - self.start
        safe = jnp.clip(lidx, 0, self.n_loc - 1)
        starts = self.row_starts[safe]
        deg = self.deg_loc[safe]
        lane = jnp.arange(width, dtype=starts.dtype)[None, :]
        rows = self.col_padded[starts[:, None] + lane]
        valid = (lane < deg[:, None]) & (ids < self.n)[:, None]
        return jnp.where(valid, rows, self.n)


jax.tree_util.register_pytree_node(
    ShardRows,
    lambda s: ((s.row_starts, s.col_padded, s.deg_loc, s.start),
               (s.n, s.n_loc, s.max_width)),
    lambda aux, ch: ShardRows(*ch, *aux),
)


# --------------------------------------------------------------------------
# the sharded super-step (one shard_map program per iteration)
# --------------------------------------------------------------------------

_STEP_CACHE: dict = {}


def _build_step(mesh, *, provider_kind: str, n: int, n_loc: int,
                tile_widths: tuple, heuristic: str, kind: str,
                pack_degrees: bool, pack_halo: bool,
                include_first_hop: bool = True, max_width: int = 1):
    """One jitted shard_map super-step: halo exchange + rotated step + swl.

    ``provider_kind`` selects how the per-shard row provider is assembled
    from the stacked plan arrays: ``"csr"`` (ShardRows over the shard's R/C
    slice) or ``"twohop"`` (a ``TwoHopRows`` whose first hop is the shard's
    dense row slice and whose second hop is replicated — repro.d2).
    ``pack_halo`` ships each halo entry as ONE ``id << 16 | color`` word
    instead of an (id, color) pair — legal whenever both provably fit
    (``n < 2**15``; colors are bounded by n), halving the exchange bytes
    the same way ``pack_degrees`` halves the neighbor gathers (§12).
    """
    if pack_halo:
        # §17 capacity guard: ids >= 2^15 flip the int32 sign bit inside
        # id << 16 and unpack as garbage neighbors — refuse, never corrupt
        from repro.errors import CapacityError
        from repro.ingest import PACKED_HALO_MAX_N, packed_halo_ok

        if not packed_halo_ok(n):
            raise CapacityError(
                f"pack_halo=True with n={n}: vertex ids must stay < "
                f"{PACKED_HALO_MAX_N} to fit the id << 16 | color halo "
                "word (int32); rerun with pack_halo=False")
    K = len(tile_widths)

    def step(prov, start, bmask, deg_ext, view, swl, *wls):
        start_s = start[0]
        bmask_l = bmask[0]
        view_l = view[0]
        swl_l = swl[0]
        wls_l = [w[0] for w in wls]

        # ---- halo exchange: live boundary (id, color) entries -------------
        send_colors = view_l[swl_l]  # sentinel n reads slot n: color 0
        if pack_halo:
            word = lax.all_gather((swl_l << 16) | send_colors, "d", tiled=True)
            all_ids = word >> 16
            all_colors = word & jnp.int32(0xFFFF)
        else:
            all_ids = lax.all_gather(swl_l, "d", tiled=True)
            all_colors = lax.all_gather(send_colors, "d", tiled=True)
        # sentinel lanes write color 0 at slot n — the pinned value, inert
        view_l = view_l.at[all_ids].set(all_colors, mode="drop")
        snapshot = view_l

        if provider_kind == "csr":
            row_starts, col_padded, deg_loc = (a[0] for a in prov)
            provider = ShardRows(row_starts, col_padded, deg_loc, start_s,
                                 n, n_loc, max_width)
        else:
            from repro.d2.coloring import TwoHopRows

            adj_a, adj_b = prov
            provider = TwoHopRows(adj_a[0], adj_b, include_first_hop,
                                  start=start_s, n_colored=n)

        # ---- rotated fused super-step, degree-tiled: every class (and
        # every shard) speculates against the same exchanged snapshot, so
        # the sharded step ≡ the single-device tiled step (§12) -------------
        new_wls, counts = [], []
        for k in range(K):
            view_l, wl_k, cnt_k = ragged_superstep(
                (lambda ids, w=tile_widths[k]: provider.rows(ids, w)),
                deg_ext, view_l, wls_l[k],
                heuristic=heuristic, kind=kind,
                colors_read=snapshot, pack_degrees=pack_degrees,
            )
            new_wls.append(wl_k)
            counts.append(cnt_k)

        # ---- next halo send list: still-live boundary vertices ------------
        live = jnp.concatenate(new_wls) if K > 1 else new_wls[0]
        lidx = live - start_s
        isb = (live < n) & bmask_l[jnp.clip(lidx, 0, n_loc - 1)]
        new_swl, scount = compact(live, isb, sentinel=n)

        out = (view_l[None], new_swl[None], jnp.stack(counts)[None],
               scount[None])
        return out + tuple(w[None] for w in new_wls)

    if provider_kind == "csr":
        prov_specs = (P("d", None), P("d", None), P("d", None))
    else:
        prov_specs = (P("d", None, None), P())
    in_specs = (prov_specs, P("d"), P("d", None), P(), P("d", None),
                P("d", None)) + tuple(P("d", None) for _ in range(K))
    out_specs = (P("d", None), P("d", None), P("d", None), P("d")) + tuple(
        P("d", None) for _ in range(K))
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))


def _get_step(mesh, devices, **cfg):
    key = (tuple(id(d) for d in devices),
           tuple(sorted(cfg.items(), key=lambda kv: kv[0])))
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = _build_step(mesh, **cfg)
    return _STEP_CACHE[key]


# --------------------------------------------------------------------------
# host driver: the fused schedule with a shard_map body + coordinated tail
# --------------------------------------------------------------------------

def run_sharded_engine(
    *,
    plan: PartitionedCSR,
    devices,
    provider_kind: str,
    prov_np: tuple,
    deg_ext_np: np.ndarray,
    classes: list,
    tile_widths: list,
    acc_widths: list,
    tail_width: int,
    tail_provider,
    heuristic: str = "degree",
    kind: str = "bitset",
    tail_enabled: bool = True,
    tail_threshold: int = 0,
    max_iters: int,
    algorithm: str,
    pack_degrees: bool = False,
    include_first_hop: bool = True,
    trace=False,
) -> ColoringResult:
    """Drive the sharded super-step to convergence (§13).

    ``classes`` are the GLOBAL degree-class id arrays (wide-first, as in
    ``run_ragged_engine``); they are split per device along the plan's
    ranges, so the union worklist — and therefore every color, live count,
    and tail decision — matches the single-device engine exactly.
    ``prov_np`` holds the stacked per-shard provider arrays
    (``plan.stack_shards`` output for ``"csr"``, ``(stacked first hop,
    replicated second hop)`` for ``"twohop"``).

    With ``trace`` (§16) each super-step records a telemetry row including
    the two sharded-only columns: ``halo_bytes`` (entries received per
    device this step × entry bytes × ndev) and ``imbalance`` (max − min
    per-shard live count).  ``max_color`` is read off the sharded view and
    may transiently include a stale remote entry mid-run; the committed
    final row is exact.  The host loop records on the host, so the
    shard_map programs are untouched either way.
    """
    if heuristic not in HEURISTICS:
        raise ValueError(
            f"unknown heuristic {heuristic!r}; options: {HEURISTICS}")
    n, ndev, L = plan.n, plan.ndev, plan.n_loc
    K = len(classes)
    mesh = Mesh(np.asarray(devices), ("d",))
    sh_vec = NamedSharding(mesh, P("d"))
    sh_row = NamedSharding(mesh, P("d", None))
    rep = NamedSharding(mesh, P())

    # ---- split classes per device (uniform caps, sentinel padding) --------
    owner_of = plan.owners()
    wls_np, caps = [], []
    counts = np.zeros((ndev, K), np.int64)
    for k, cls in enumerate(classes):
        groups = [cls[owner_of[cls] == d] for d in range(ndev)]
        cap = max(max((g.size for g in groups), default=0), 1)
        arr = np.full((ndev, cap), n, np.int32)
        for d, g_ids in enumerate(groups):
            arr[d, : g_ids.size] = g_ids
            counts[d, k] = g_ids.size
        wls_np.append(arr)
        caps.append(cap)

    # ---- device placement -------------------------------------------------
    if provider_kind == "csr":
        prov = tuple(jax.device_put(jnp.asarray(a), sh_row) for a in prov_np)
    else:
        adj_a_np, adj_b_np = prov_np
        prov = (
            jax.device_put(jnp.asarray(adj_a_np),
                           NamedSharding(mesh, P("d", None, None))),
            jax.device_put(jnp.asarray(adj_b_np), rep),
        )
    start_dev = jax.device_put(
        jnp.asarray(plan.starts[:-1].astype(np.int32)), sh_vec)
    bmask_dev = jax.device_put(jnp.asarray(plan.boundary_masks()), sh_row)
    deg_dev = jax.device_put(jnp.asarray(deg_ext_np), rep)
    # bootstrap identity (§12): every real vertex takes color 1 — a constant
    # every device already agrees on, so the first step needs no exchange
    boot = (np.arange(n + 1, dtype=np.int32) < n).astype(np.int32)
    view = jax.device_put(jnp.asarray(np.tile(boot, (ndev, 1))), sh_row)
    wls = [jax.device_put(jnp.asarray(a), sh_row) for a in wls_np]
    swl = jax.device_put(jnp.full((ndev, 1), n, jnp.int32), sh_row)
    scounts = np.zeros(ndev, np.int64)

    cells_per_step = sum(ndev * caps[k] * acc_widths[k] for k in range(K))
    total = int(counts.sum())
    prev = total
    iters = 1  # the materialized bootstrap
    work = 0   # post-step live totals (fused accounting)
    padded = 0
    halo_bytes = 0
    stalled = False
    from repro.ingest import packed_halo_ok

    pack_halo = packed_halo_ok(n)  # id and color both provably fit 15/16 bits
    halo_entry_bytes = 4 if pack_halo else 8
    # ONE cached jitted step per config; the pow2-resliced swl width below
    # retraces it per distinct shape exactly as jit always does
    step = _get_step(
        mesh, devices, provider_kind=provider_kind, n=n, n_loc=L,
        tile_widths=tuple(tile_widths), heuristic=heuristic, kind=kind,
        pack_degrees=pack_degrees, pack_halo=pack_halo,
        include_first_hop=include_first_hop, max_width=tail_width)
    trace_cap = resolve_trace_cap(trace, max_iters)
    rows = []
    if trace_cap:
        # the materialized bootstrap: everyone takes color 1, nothing retires
        rows.append((total, 0, total, 1, 0, 0, 0, 0))
    with span("superstep_loop", mode="sharded", ndev=ndev):
        while total > 0 and iters < max_iters:
            if tail_enabled and total <= tail_threshold:
                break
            if tail_enabled and _stalled(iters, total, prev):
                stalled = True
                break
            prev = total
            cap_s = min(next_pow2(max(int(scounts.max(initial=0)), 1)),
                        int(swl.shape[1]))
            # the count reads wait for the devices: the span holds the step
            with span("superstep", step=iters, live=total,
                      cells=cells_per_step):
                out = step(prov, start_dev, bmask_dev, deg_dev, view,
                           swl[:, :cap_s], *wls)
                view, swl, counts_dev, scounts_dev = out[:4]
                counts = np.asarray(counts_dev)
                scounts = np.asarray(scounts_dev)
            wls = list(out[4:])
            # received per device: ndev × cap_s halo entries (padded lanes too)
            step_halo = halo_entry_bytes * ndev * cap_s
            halo_bytes += step_halo
            iters += 1
            new_total = int(counts.sum())
            if trace_cap:
                per_shard = counts.sum(axis=1)
                rows.append((total, total - new_total, new_total,
                             int(jnp.max(view)), cells_per_step, 0,
                             step_halo,
                             int(per_shard.max() - per_shard.min())))
            total = new_total
            work += total
            padded += cells_per_step

    converged = total == 0
    deg_ext_loc = jnp.asarray(deg_ext_np)
    tail_cells = 0
    if total > 0 and iters < max_iters and tail_enabled:
        # coordinated tail: gather survivors to one device, one ordered
        # serial FirstFit pass, scatter back by range assembly
        with span("serial_tail", live=total, stalled=stalled):
            colors_ext = jnp.asarray(_assemble(view, plan))
            if stalled:
                tail_wl = order_tail(jnp.arange(n, dtype=jnp.int32),
                                     deg_ext_loc)
            else:
                flat = np.concatenate(
                    [np.asarray(w).reshape(-1) for w in wls]).astype(np.int32)
                tail_wl = order_tail(jnp.asarray(flat), deg_ext_loc)
            colors_ext = provider_tail(tail_provider, colors_ext, tail_wl,
                                       kind=kind)
            colors_ext.block_until_ready()  # the readback waits for it anyway
        work += n if stalled else total
        tail_cells = int(tail_wl.shape[0]) * tail_width
        padded += tail_cells
        iters += 1
        converged = True
        colors = np.asarray(colors_ext[:n])
        if trace_cap:
            rows.append((total, total, 0, int(colors.max(initial=0)),
                         tail_cells, 1, 0, 0))
    else:
        colors = _assemble(view, plan)[:n]
    result = ColoringResult(
        colors, iters, work + n, padded, converged, algorithm=algorithm,
        halo_bytes_per_step=halo_bytes / max(iters, 1),
    )
    if trace_cap:
        result.trace = assemble_trace(rows, iters, trace_cap,
                                      f"{algorithm}:sharded")
    return result


def _assemble(view, plan: PartitionedCSR) -> np.ndarray:
    """Global ``colors_ext`` from the per-device views (own ranges only)."""
    views = np.asarray(view)
    out = np.zeros(plan.n + 1, np.int32)
    for d in range(plan.ndev):
        s, e = int(plan.starts[d]), int(plan.starts[d + 1])
        out[s:e] = views[d, s:e]
    return out


# --------------------------------------------------------------------------
# distance-1 entry point (repro.api reaches this via engine="sharded")
# --------------------------------------------------------------------------

def color_distributed(
    g: CSRGraph,
    *,
    devices=None,
    heuristic: str = "degree",
    firstfit: str = "bitset",
    buckets: tuple = (),
    tiling="auto",
    tail_serial="auto",
    max_iters: int | None = None,
    trace=False,
) -> ColoringResult:
    """Color ``g`` on every available device with the sharded engine (§13).

    Bit-identical to single-device ``color_data_driven`` (any engine/mode)
    by the snapshot argument above; per-step communication is one halo
    exchange of live boundary colors instead of two full-array all-gathers.
    Runs the full shard_map machinery even on one device (useful for
    in-process testing); the *api* layer is what falls back to ``ragged``
    there.
    """
    if heuristic not in HEURISTICS:
        raise ValueError(
            f"unknown heuristic {heuristic!r}; options: {HEURISTICS}")
    devices = list(devices) if devices is not None else jax.devices()
    ndev = len(devices)
    n = g.n
    if n == 0:
        result = ColoringResult(np.zeros(0, np.int32), 0, 0, 0, True,
                                algorithm=f"sharded_sgr_{ndev}dev")
        if trace:
            result.trace = empty_trace(f"sharded_sgr_{ndev}dev")
        return result
    max_iters = max_iters or n + 1

    def run():
        with span("partition_plan", ndev=ndev):
            plan = _graph_device_cache(
                g, f"plan{ndev}", lambda: PartitionedCSR.from_graph(g, ndev))
            classes, widths = _resolve_classes(g.degrees, buckets, tiling)
        with span("csr_build", engine="sharded"):
            prov_np = _graph_device_cache(
                g, f"shards{ndev}", lambda: plan.stack_shards(g))
            tail_provider = _graph_device_cache(
                g, "dcsr", lambda: DeviceCSR.from_csr(g))
        dmax = max(g.max_degree, 1)
        deg_ext_np = np.concatenate(
            [g.degrees, np.zeros(1, np.int32)]).astype(np.int32)
        tail_enabled, thr = resolve_tail_threshold(tail_serial, n)
        return run_sharded_engine(
            plan=plan, devices=devices, provider_kind="csr", prov_np=prov_np,
            deg_ext_np=deg_ext_np, classes=classes, tile_widths=widths,
            acc_widths=widths, tail_width=dmax, tail_provider=tail_provider,
            heuristic=heuristic, kind=firstfit, tail_enabled=tail_enabled,
            tail_threshold=thr, max_iters=max_iters,
            algorithm=f"sharded_sgr_{ndev}dev",
            pack_degrees=_packed_gather_ok(dmax),
            trace=trace,
        )

    if not trace:
        return run()
    with SpanRecorder() as rec:
        result = run()
    if result.trace is not None:
        result.trace.spans = rec.events
    return result
