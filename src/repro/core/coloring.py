"""Data-driven speculative-greedy GPU coloring (paper Alg. 7) in JAX.

The paper's contribution, adapted to the TPU/XLA execution model (DESIGN.md §3):

* worklist double-buffering          -> functional carry swap
* atomic push -> CUB prefix sum      -> ``jnp.cumsum`` compaction (identical math)
* color clearing on conflict          -> kept verbatim (correctness-critical here too)
* kernel fusion + global barrier      -> each super-step is ONE jitted XLA
                                         computation; the loop carry is the barrier
* thread coarsening                   -> sequential chunks per super-step (fewer
                                         concurrent speculations -> fewer conflicts)
* Merrill load balancing              -> degree classes, each processed at its own
                                         tile width

Two execution ENGINES (DESIGN.md §12):

* ``ragged`` (default) — the CSR-native rotated super-step: ONE adjacency
  gather and ONE neighbor-color gather per iteration serve BOTH conflict
  detection and FirstFit; degree-tiled dispatch sizes each worklist class's
  gather to its own tile width (O(edges) traffic, not O(n·Δmax)); adaptive
  tail-serialization collapses slow-shrinking worklist cascades into one
  sequential-on-device FirstFit pass that is conflict-free by construction.
* ``padded`` — the same schedule dispatched through the original dense
  ``(n, Δmax)`` padded-adjacency table.  Padding lanes are sentinel-inert, so
  ``padded`` and ``ragged`` produce bit-identical colors — the engines differ
  only in memory layout and bandwidth (tested).
* ``classic`` — the pre-§12 two-phase super-step (FirstFit kernel, then a
  separate ConflictResolve kernel re-gathering the tiles), kept as the
  paper-faithful baseline and for A/B benchmarking.

Two execution modes, orthogonal to the engine:

* ``workefficient`` (default) — host loop; each class's worklist buffer is
  re-sliced to the next power of two of its live count each super-step.
* ``fused`` — a single ``lax.while_loop`` over full-capacity buffers: the
  speculative phase is one device program (plus at most one serial-tail
  dispatch), what you deploy on TPU where re-dispatch is expensive.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.api import register
from repro.core.csr import CSRGraph, DeviceCSR, auto_tile_thresholds, next_pow2
from repro.core.firstfit import FF_FUNCS
from repro.core.heuristics import conflict_lose_flags, conflict_lose_lanes
from repro.obs.spans import SpanRecorder, span
from repro.obs.trace import (
    assemble_trace,
    empty_trace,
    resolve_trace_cap,
    ring_init,
    ring_rows,
)

__all__ = [
    "ColoringResult",
    "DenseRows",
    "color_data_driven",
    "color_fused",
    "fused_result",
    "order_tail",
    "provider_tail",
    "ragged_superstep",
    "run_fused_loop",
    "run_ragged_engine",
    "run_workefficient_loop",
    "resolve_tail_threshold",
    "serial_tail_step",
]

# Row providers travel INTO module-level jitted engine functions as pytrees,
# so jit compilations are keyed on (provider type, aux config, array shapes)
# and cached across color() calls — never on per-call Python closures.
jax.tree_util.register_pytree_node(
    DeviceCSR,
    lambda d: ((d.row_starts, d.col_padded, d.deg_ext), (d.n, d.max_width)),
    lambda aux, ch: DeviceCSR(*ch, *aux),
)

# Adaptive tail-serialization: the worklist "stalls" when a super-step
# retires less than 1 - STALL_NUM/STALL_DEN of it.  Cascading graphs (grids,
# circuits, roads) shrink by ~0.1-1%/step for tens to hundreds of steps —
# the stall detector hands those to the serial tail after ~3 steps, where
# one sequential pass crosses the whole frontier.  Integer math so host and
# device drivers decide identically (int32-safe far past this repo's suite
# sizes).
STALL_NUM, STALL_DEN = 9, 10


def _packed_gather_ok(dmax: int, color_bound: int | None = None) -> bool:
    """§17 capacity predicate for the color|deg<<16 packed gather (lazy
    import — ``repro.ingest`` imports ``core.csr`` through the package)."""
    from repro.ingest import packed_gather_ok

    return packed_gather_ok(dmax, color_bound)


@dataclasses.dataclass
class ColoringResult:
    colors: np.ndarray
    iterations: int
    work_items: int          # worklist entries actually live across super-steps
    padded_work: int         # gather cells dispatched: Σ lanes × tile width
    converged: bool
    algorithm: str = "data_driven_sgr"
    # sharded engine only (§13): bytes of boundary colors a device receives
    # per super-step, averaged over the run; 0 on single-device engines
    halo_bytes_per_step: float = 0.0
    # per-degree-class gather-cell accounting (§15): ``(width, cells)`` pairs
    # for every class that dispatched work (the serial tail contributes a
    # final full-width entry).  Partitions ``padded_work`` — the roofline
    # model (benchmarks/roofline.py) turns it into bytes moved per class.
    class_cells: tuple = ()
    # per-super-step telemetry (§16): a ``repro.obs.RunTrace`` when the run
    # was traced (``trace=True``), else None.  ``trace`` is a STATIC knob —
    # untraced runs compile the identical program and stay bit-identical.
    trace: object = None
    # §17 robustness ledger: every deviation from the clean fast path —
    # ingest repairs applied to the input, guarantee-ladder escalations
    # taken to reach a valid coloring — as JSON-safe dicts with a "stage"
    # key.  Empty on every healthy run; the CI regression gate fails on
    # unexpected entries in BENCH records.
    degradations: tuple = ()

    @property
    def num_colors(self) -> int:
        return int(self.colors.max(initial=0))


# --------------------------------------------------------------------------
# phase helpers (shared with topo.py / threestep.py / distributed.py)
# --------------------------------------------------------------------------

def gather_rows(adj: jax.Array, ids: jax.Array, sentinel: int | None = None) -> jax.Array:
    """Gather padded adjacency rows; sentinel ids yield all-sentinel rows.

    ``sentinel`` is the fill value for masked rows and defaults to the row
    count (square adjacency).  Rectangular compositions — the bipartite
    cols→rows hop, whose *output* ids live on the other side (repro.d2) —
    pass the target side's sentinel explicitly.
    """
    n = adj.shape[0]
    fill = n if sentinel is None else sentinel
    rows = adj[jnp.clip(ids, 0, n - 1)]
    return jnp.where((ids < n)[:, None], rows, fill)


def take_tile(x: jax.Array, rows: jax.Array) -> jax.Array:
    """``x[rows]`` for a ``(w, W)`` tile of in-bounds ids.

    Same values as plain indexing, in the gather form the TPU compiler
    builds fast: the default clamping gather of a 65,536 x 376 tile takes
    it about 70 s on v5e, ``mode="fill"`` under a second.
    """
    return jnp.take(x, rows, mode="fill", fill_value=0)


def ff_apply(adj, colors_ext, ids, kind: str, use_kernel: bool = False,
             rows=None):
    """FirstFit the worklist chunk ``ids`` and write colors (sentinel-safe)."""
    n = adj.shape[0]
    rows = gather_rows(adj, ids) if rows is None else rows
    nc = take_tile(colors_ext, rows)
    if use_kernel:
        from repro.kernels.firstfit.ops import firstfit_bitset_tpu

        c = firstfit_bitset_tpu(nc)
    else:
        c = FF_FUNCS[kind](nc)
    c = jnp.where(ids < n, c, 0).astype(colors_ext.dtype)
    return colors_ext.at[ids].set(c)


def cr_flags(adj, deg_ext, colors_ext, ids, heuristic: str,
             use_kernel: bool = False, rows=None):
    """Conflict flags for the worklist chunk ``ids`` (True = loses, recolor)."""
    rows = gather_rows(adj, ids) if rows is None else rows
    my_c = colors_ext[ids]
    nc = take_tile(colors_ext, rows)
    my_d = deg_ext[ids]
    nd = take_tile(deg_ext, rows)
    if use_kernel:
        from repro.kernels.conflict.ops import conflict_tpu

        return conflict_tpu(ids, rows, my_c, nc, my_d, nd, heuristic)
    return conflict_lose_flags(ids, rows, my_c, nc, my_d, nd, heuristic)


def compact(ids: jax.Array, flags: jax.Array, sentinel: int):
    """Prefix-sum worklist compaction (the paper's CUB scan, §3.1)."""
    cap = ids.shape[0]
    pos = jnp.cumsum(flags.astype(jnp.int32)) - 1
    out = jnp.full((cap,), sentinel, dtype=ids.dtype)
    out = out.at[jnp.where(flags, pos, cap)].set(ids, mode="drop")
    return out, jnp.sum(flags.astype(jnp.int32))


def _chunk_bounds(cap: int, nchunks: int):
    nchunks = max(1, min(nchunks, cap))
    size = math.ceil(cap / nchunks)
    return [(i * size, min((i + 1) * size, cap)) for i in range(nchunks)
            if i * size < cap]


# --------------------------------------------------------------------------
# classic super-step: FirstFit -> ConflictResolve(+clear) -> compaction
# --------------------------------------------------------------------------

@partial(
    jax.jit,
    static_argnames=("heuristic", "kind", "coarsen_ff", "coarsen_cr",
                     "use_kernel", "reuse_rows"),
)
def sgr_step(
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
    reuse_rows: bool = False,
):
    n = adj.shape[0]
    cap = wl.shape[0]

    # §Perf iteration: FirstFit and ConflictResolve gather the same adjacency
    # rows; with aligned (un)chunking the gather can be done once per step.
    rows_all = gather_rows(adj, wl) if (
        reuse_rows and coarsen_ff == 1 and coarsen_cr == 1) else None

    # ---- FirstFit phase (coarsened: later chunks see earlier chunk colors) --
    for lo, hi in _chunk_bounds(cap, coarsen_ff):
        colors_ext = ff_apply(adj, colors_ext, wl[lo:hi], kind, use_kernel,
                              rows=rows_all)

    # ---- ConflictResolve + color clearing (paper §3.1) ----------------------
    lose_parts = []
    for lo, hi in _chunk_bounds(cap, coarsen_cr):
        ids = wl[lo:hi]
        lose = cr_flags(adj, deg_ext, colors_ext, ids, heuristic, use_kernel,
                        rows=rows_all)
        colors_ext = colors_ext.at[ids].set(
            jnp.where(lose, 0, colors_ext[ids])
        )
        lose_parts.append(lose)
    lose = jnp.concatenate(lose_parts) if len(lose_parts) > 1 else lose_parts[0]

    # ---- worklist compaction (double buffering = functional swap) -----------
    new_wl, new_count = compact(wl, lose, sentinel=n)
    return colors_ext, new_wl, new_count


# --------------------------------------------------------------------------
# the rotated (fused) super-step — ONE gather serves both phases (§12)
# --------------------------------------------------------------------------
# Key observation: a worklist vertex FirstFits a color that is, by
# construction, distinct from every color visible in its gathered tile — so
# fresh conflicts can only involve OTHER worklist vertices recolored in the
# same step.  Rotating the loop (verify the previous step's speculation, then
# immediately recolor the losers from the SAME tile) therefore needs exactly
# one adjacency gather and one neighbor-color gather per iteration, where the
# classic two-phase step pays both twice.  Every vertex this step recolors is
# re-verified next step; termination (nobody recolored) certifies validity.

def ragged_superstep(rows_fn, deg_ext, colors_ext, wl, *,
                     heuristic: str = "degree", kind: str = "bitset",
                     use_kernel: bool = False, coarsen: int = 1,
                     colors_read=None, pack_degrees: bool = False,
                     provider=None, width: int | None = None):
    """One rotated super-step: ConflictResolve + FirstFit + compaction.

    ``rows_fn(ids) -> (w, W)`` provides the sentinel-padded neighbor tile —
    a ``DeviceCSR`` class gather, a dense padded-row gather, or a composed
    two-hop gather (repro.d2); the engine is generic over the row provider.
    ``coarsen`` chunks the worklist so later chunks observe earlier chunks'
    recolorings (the thread-coarsening knob, fewer concurrent speculations).

    ``colors_read`` is the snapshot the FIRST chunk reads (later chunks read
    the accumulating state).  Degree-tiled drivers pass the iteration-start
    snapshot so every class speculates against the same state — which makes
    a tiled super-step bit-identical to the single-class one (classes
    partition the worklist and their writes are disjoint).

    ``pack_degrees`` fuses the neighbor-color and neighbor-degree gathers
    into ONE gather of ``color | degree << 16`` words — degrees are static
    and an O(n) repack per step is far cheaper than a second (w, W) scattered
    gather.  Callers enable it when both fields provably fit 15 bits (colors
    are bounded by the gather width + 1).  Packed or not, the arithmetic is
    exact, so results are bit-identical either way.

    ``use_kernel="csr"`` (backend="pallas-csr", DESIGN.md §18) routes the
    step through the CSR-resident fused kernel when ``provider`` is a
    ``DeviceCSR`` and the packed word fits (``pack_degrees``): the kernel
    gathers neighbors straight from R/C in VMEM — no ``rows_fn`` call and no
    materialized ``(w, W)`` tile.  Configurations the CSR kernel can't serve
    (dense providers, chunked worklists, packed overflow) fall back to the
    gathered kernel — bit-identical by the §15 argument.
    """
    n = colors_ext.shape[0] - 1
    cap = wl.shape[0]
    read = colors_ext if colors_read is None else colors_read
    chunk_bounds = _chunk_bounds(cap, coarsen)
    # the packed word array must track earlier chunks' writes, so a chunked
    # step would repack O(n) per chunk — fall back to separate gathers there
    pack_degrees = pack_degrees and len(chunk_bounds) == 1
    use_csr = (use_kernel == "csr" and pack_degrees
               and isinstance(provider, DeviceCSR))
    need_parts = []
    for lo, hi in chunk_bounds:
        ids = wl[lo:hi]
        if use_csr:
            from repro.kernels.superstep.csr_kernel import superstep_csr_tpu

            packed = read + (deg_ext << 16)
            new_c, need = superstep_csr_tpu(
                provider.row_starts, provider.col_padded, packed, ids,
                provider.max_width if width is None else width, heuristic)
            valid = ids < n
            need = need & valid
            new_c = jnp.where(valid, new_c, 0).astype(colors_ext.dtype)
            colors_ext = colors_ext.at[ids].set(new_c)
            read = colors_ext
            need_parts.append(need)
            continue
        rows = rows_fn(ids)
        my_c = read[ids]
        my_d = deg_ext[ids]
        if pack_degrees and not use_kernel:
            tile = take_tile(read + (deg_ext << 16), rows)
            nc = tile & jnp.int32(0xFFFF)
            nd = tile >> 16
        else:
            nc = take_tile(read, rows)
            nd = take_tile(deg_ext, rows)
        if use_kernel:
            from repro.kernels.superstep.ops import superstep_tpu

            new_c, need = superstep_tpu(ids, rows, my_c, nc, my_d, nd,
                                        heuristic)
        else:
            same, lose_lane = conflict_lose_lanes(ids, rows, my_c, nc, my_d,
                                                  nd, heuristic)
            need = jnp.any(lose_lane, axis=1) | (my_c == 0)
            # lanes I beat are provably recoloring too — refit as if cleared
            # (the classic engine's clear-then-refit dynamics, in one pass)
            ff_nc = jnp.where(same & ~lose_lane, 0, nc)
            new_c = jnp.where(need, FF_FUNCS[kind](ff_nc), my_c)
        valid = ids < n
        need = need & valid
        new_c = jnp.where(valid, new_c, 0).astype(colors_ext.dtype)
        colors_ext = colors_ext.at[ids].set(new_c)
        read = colors_ext  # later chunks observe earlier chunks' writes
        need_parts.append(need)
    need = jnp.concatenate(need_parts) if len(need_parts) > 1 else need_parts[0]
    new_wl, new_count = compact(wl, need, sentinel=n)
    return colors_ext, new_wl, new_count


def serial_tail_step(row1_fn, colors_ext, wl, kind: str = "bitset"):
    """Sequential-on-device FirstFit over ``wl`` — conflict-free by construction.

    A ``fori_loop`` walks the worklist one vertex at a time and re-FirstFits
    it against the *current* state — the canonical sequential-greedy choice,
    which both guarantees zero conflicts on every edge incident to the
    worklist (later vertices observe earlier updates) and sheds the inflated
    colors speculation may have piled up before the engine bailed out: the
    whole cascade tail costs ONE super-step.  ``row1_fn(v) -> (W,)`` is the
    single-vertex row provider (``DeviceCSR.gather_row1``, a dense row, or a
    composed two-hop row).

    The worklist's colors are cleared up front, so each refit sees only the
    colors of settled (non-worklist) vertices and of already-processed tail
    entries — pure sequential greedy with the winners pinned.  Clearing also
    makes self/duplicate lanes in composed two-hop rows trivially inert.
    """
    n = colors_ext.shape[0] - 1
    colors_ext = colors_ext.at[wl].set(0)  # sentinel entries write slot n: 0

    def body(i, colors_ext):
        v = wl[i]
        nc = colors_ext[row1_fn(v)]
        ff = FF_FUNCS[kind](nc[None, :])[0]
        new_c = jnp.where(v < n, ff, 0)
        return colors_ext.at[v].set(new_c.astype(colors_ext.dtype))

    return lax.fori_loop(0, wl.shape[0], body, colors_ext)


def order_tail(wl, deg_ext):
    """Canonical serial-tail order: degree-descending, ties id-ascending.

    Largest-degree-first is the classic greedy quality ordering and matches
    the engine's conflict heuristic; sentinels sort last.  One shared
    device-side implementation so the host, fused, and batched drivers
    produce the exact same sequence (bit-identical colors).
    """
    n = deg_ext.shape[0] - 1
    ids = jnp.sort(wl)                       # id-ascending, sentinels last
    key = jnp.where(ids < n, -deg_ext[ids], jnp.iinfo(jnp.int32).max)
    return ids[jnp.argsort(key, stable=True)]


def resolve_tail_threshold(tail_serial, n: int) -> tuple[bool, int]:
    """(enabled, live-count threshold) from the ``tail_serial`` option.

    ``"auto"`` picks a count below which one sequential pass beats the
    expected remaining super-step dispatches; ``None``/``0`` disables both
    the threshold and the stall detector (pure speculative, pre-§12
    semantics); an int is an explicit threshold.
    """
    if tail_serial in (None, 0, False):
        return False, 0
    if tail_serial == "auto":
        return True, int(min(1024, max(32, n // 64)))
    return True, max(1, int(tail_serial))


def _stalled(iters, total, prev) -> bool:
    """Worklist stall: the last step retired < 1/STALL_DEN of the worklist.

    ``iters >= 3`` skips the bootstrap step (everyone is uncolored, so the
    first rotated step never shrinks the worklist by construction) AND the
    first conflict wave (which retires only the conflict-component winners —
    a large worklist regardless of topology).  From the third step on, a
    near-unit shrink ratio is the signature of a cascading grid/circuit
    graph whose frontier the serial tail crosses in one pass.
    """
    return (iters >= 3) & (total * STALL_DEN >= STALL_NUM * prev)


def _class_cells(acc_widths, cells_k, tail_width: int, tail_cells: int):
    """Assemble ``ColoringResult.class_cells``: ``(width, cells)`` per class.

    Zero-cell classes are dropped (a class that never dispatched moved no
    bytes); a serial-tail pass contributes one final full-width entry.  The
    remaining entries always partition ``padded_work`` exactly — the
    invariant the roofline unit tests assert.
    """
    out = [(int(w), int(c)) for w, c in zip(acc_widths, cells_k) if c]
    if tail_cells:
        out.append((int(tail_width), int(tail_cells)))
    return tuple(out)


# --------------------------------------------------------------------------
# row providers (pytrees) + module-level jitted engine entry points
# --------------------------------------------------------------------------

class DenseRows:
    """Dense padded-adjacency row provider (the ``padded`` engine layout).

    ``rows``/``row1`` mirror the ``DeviceCSR`` provider protocol so the same
    engine drivers run over either storage; ``width`` requests are ignored —
    a dense table always gathers its full (Δmax) width, which is exactly the
    bandwidth difference the engines A/B.
    """

    def __init__(self, adj, sentinel: int | None = None):
        self.adj = adj
        self.sentinel = int(adj.shape[0]) if sentinel is None else int(sentinel)

    def rows(self, ids, width: int | None = None):
        return gather_rows(self.adj, ids, self.sentinel)

    def row1(self, v):
        n = self.adj.shape[0]
        r = self.adj[jnp.clip(v, 0, n - 1)]
        return jnp.where(v < n, r, self.sentinel)


jax.tree_util.register_pytree_node(
    DenseRows,
    lambda d: ((d.adj,), (d.sentinel,)),
    lambda aux, ch: DenseRows(*ch, *aux),
)


@partial(jax.jit, static_argnames=("kind",))
def provider_tail(provider, colors_ext, wl, *, kind="bitset"):
    """``serial_tail_step`` over a pytree row provider (cached compilation)."""
    return serial_tail_step(provider.row1, colors_ext, wl, kind)


# --------------------------------------------------------------------------
# the round tail: serial_tail_step's coloring, one dependency round at a time
# --------------------------------------------------------------------------
# Sequential greedy in a fixed order gives a vertex the smallest color no
# neighbor holds at its turn: settled neighbors (outside the tail) and the
# tail neighbors earlier in the order (its predecessors); later ones are
# still cleared.  So a vertex can take its color as soon as every
# predecessor has one, and every tail vertex whose predecessors are all
# colored can take it in the same round: such vertices are never adjacent
# (of two adjacent tail vertices one precedes the other).  The state array
# holds -1 for an uncolored tail vertex, so one gather of a round's tile
# gives both the colors FirstFit reads (-1 reads as 0) and the successors
# whose predecessor counters the round decrements.  The number of rounds is
# the longest predecessor chain in the order.

ROUND_CAP_FLOOR = 256  # fewest lanes a round dispatches per class (jit keys)
TAIL_MIN_WIDTH = 16    # narrowest tail class (jit keys)
PREFIX_BLOCK = 1024    # lanes per block of the blocked prefix count


def _tail_tile(provider, ids, width):
    """``provider.rows(ids, width).T``: the round tail's lane-major tile.

    ``DeviceCSR`` gathers it lane-major (``gather_rows_t``): the tail's
    classes are narrow (rmat-g's largest holds 872,309 vertices of degree
    at most 16), and a ``(w, 16)`` tile pads every row to the TPU's 128
    lanes.
    """
    if isinstance(provider, DeviceCSR):
        return provider.gather_rows_t(
            ids, provider.max_width if width is None else width)
    return provider.rows(ids, width).T


def _prefix_sum(flags):
    """Running sum of ``flags`` (bool or int32) along the last axis, in blocks.

    Same numbers as ``jnp.cumsum``; the v5e compiler takes about 26 s over
    one 2^20-long cumsum and under 2 s over 1,024-wide blocks.
    """
    n = flags.shape[-1]
    pad = -n % PREFIX_BLOCK
    f = jnp.pad(flags.astype(jnp.int32), [(0, 0)] * (flags.ndim - 1)
                + [(0, pad)])
    f = f.reshape(*flags.shape[:-1], -1, PREFIX_BLOCK)
    within = jnp.cumsum(f, axis=-1, dtype=jnp.int32)
    block = within[..., -1]
    before = jnp.cumsum(block, axis=-1, dtype=jnp.int32) - block
    out = within + before[..., None]
    return out.reshape(*flags.shape[:-1], -1)[..., :n]


def _csr_lanes_below(provider, key):
    """For each vertex ``v < n``, the lanes of its ``DeviceCSR`` row whose
    neighbor ``u`` has ``key[u] < key[v]``: the lanes ``gather_rows``
    returns, counted along C itself, so no lane is padding.

    Each entry's row key comes from a running sum of key differences put
    at the row starts (rows that share a start, the empty ones, cancel),
    so ``|key|`` must stay below 2^30.
    """
    n = provider.n
    starts = provider.row_starts[:n]
    diff = key[:n] - jnp.concatenate([jnp.zeros(1, key.dtype), key[:n - 1]])
    # row starts are sorted: saying so takes the v5e compiler from ~12 s to
    # under 1 s over rmat-g's 2^20 starts into 10.5M entries
    marks = jnp.zeros(provider.col_padded.shape, key.dtype).at[starts].add(
        diff, indices_are_sorted=True)
    row_key = _prefix_sum(marks)
    below = take_tile(key, provider.col_padded) < row_key
    seen = jnp.concatenate([jnp.zeros(1, jnp.int32), _prefix_sum(below)])
    return (jnp.take(seen, starts + provider.deg_ext[:n], mode="fill",
                     fill_value=0) - seen[starts])


@partial(jax.jit, static_argnames=("widths", "by_entries"))
def provider_tail_setup(provider, colors_ext, tail_wl, members, *, widths,
                        by_entries=False):
    """State, predecessor counters and class of every tail vertex.

    ``tail_wl`` is the tail in ``order_tail`` order (sentinel padding last);
    ``members[k]`` holds the tail vertices of class ``k`` (sentinel-padded),
    whose rows ``provider.rows(ids, widths[k])`` gathers.  Ranks are
    positions in ``tail_wl``; settled vertices and the sentinel rank past
    every tail vertex, so they are never counted as predecessors.
    ``by_entries`` counts along a ``DeviceCSR``'s C once instead of over
    the classes' padded tiles: the same lanes, the same counts.
    """
    n = colors_ext.shape[0] - 1
    last = tail_wl.shape[0]
    rank = jnp.full((n + 1,), last, jnp.int32).at[tail_wl].set(
        jnp.arange(last, dtype=jnp.int32)).at[n].set(last)
    state = colors_ext.at[tail_wl].set(-1).at[n].set(0)
    cls = jnp.full((n + 1,), len(members), jnp.int32)
    for k, ids in enumerate(members):
        cls = cls.at[ids].set(k)
    cls = cls.at[n].set(len(members))
    if by_entries:
        cnt = jnp.concatenate([_csr_lanes_below(provider, rank),
                               jnp.zeros(1, jnp.int32)])
        return state, jnp.where(rank < last, cnt, 0), cls
    cnt = jnp.zeros((n + 1,), jnp.int32)
    for ids, width in zip(members, widths):
        pred = take_tile(rank, _tail_tile(provider, ids, width)) < rank[ids]
        cnt = cnt.at[ids].set(jnp.sum(pred, axis=0, dtype=jnp.int32))
    return state, cnt, cls


@partial(jax.jit, static_argnames=("classes",))
def provider_tail_frontier(state, cnt, cls, *, classes):
    """Per-class running counts of the uncolored tail vertices with no
    uncolored predecessor: row ``k`` of the ``(classes, n)`` counts numbers
    class ``k``'s frontier; its last column is the class count."""
    n = state.shape[0] - 1
    ready = (state[:n] == -1) & (cnt[:n] == 0)
    member = cls[None, :n] == jnp.arange(classes, dtype=jnp.int32)[:, None]
    csum = _prefix_sum(ready[None, :] & member)
    return csum, csum[:, -1]


def _nth_true(csum, cap):
    """Where the running count ``csum`` first reaches 1, 2, ..., ``cap``:
    the positions of the first ``cap`` true flags, ``n`` past the last.

    ``jnp.searchsorted`` halves all n positions, one gather of ``cap``
    lanes per halving.  Here one compare against the last count of each
    of at most 1,024 blocks finds the block, and the search halves only
    its positions: 11 gathers, not 21, at rmat-g's n = 2^20.
    """
    n = csum.shape[0]
    block = max(PREFIX_BLOCK, next_pow2(-(-n // PREFIX_BLOCK)))
    j = jnp.arange(1, cap + 1, dtype=csum.dtype)
    ends = csum[jnp.minimum(jnp.arange(block - 1, n + block - 1, block),
                            n - 1)]
    lo = jnp.sum(ends[None, :] < j[:, None], axis=1, dtype=jnp.int32)
    lo = jnp.minimum(lo * block, n)
    hi = jnp.minimum(lo + block, n)

    def halve(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        right = (lo < hi) & (jnp.take(csum, mid, mode="fill",
                                      fill_value=cap + 1) < j)
        return (jnp.where(right, mid + 1, lo),
                jnp.where((lo < hi) & ~right, mid, hi))

    lo, _ = lax.fori_loop(0, block.bit_length(), halve, (lo, hi))
    return lo


@partial(jax.jit, static_argnames=("cap", "width", "kind"))
def provider_tail_round(provider, state, cnt, csum, k, *, cap, width, kind):
    """Color class ``k``'s frontier (at most ``cap`` vertices) by FirstFit
    and take them off their successors' predecessor counters."""
    n = state.shape[0] - 1
    ids = _nth_true(csum[k], cap)
    rows = _tail_tile(provider, ids, width)
    nc = take_tile(state, rows)
    valid = ids < n
    c = jnp.where(valid, FF_FUNCS[kind](jnp.maximum(nc, 0).T), 0)
    state = state.at[ids].set(c.astype(state.dtype))
    # an uncolored tail lane of a frontier vertex is one of its successors
    succ = (nc < 0) & valid
    cnt = cnt.at[jnp.where(succ, rows, n + 1)].add(-1, mode="drop")
    return state, cnt


def _tail_classes(provider, tail_ids):
    """(class id arrays, widths) that ``round_tail`` splits the tail into.

    A ``DeviceCSR`` tail splits by row length rounded up to a power of two
    (at least ``TAIL_MIN_WIDTH``, at most the provider's width), so a
    tile's lanes are at most twice its entries; the engine's degree
    classes are wider (rmat-g's reach from degree 33 to 376).  Other
    providers gather their full width whatever is asked: one class.
    """
    if not isinstance(provider, DeviceCSR):
        return [tail_ids], [None]
    deg = np.asarray(provider.deg_ext)[tail_ids]
    widths = np.minimum(
        1 << np.ceil(np.log2(np.maximum(deg, TAIL_MIN_WIDTH))).astype(int),
        provider.max_width)
    ws = sorted(set(widths.tolist()), reverse=True)
    return [tail_ids[widths == w] for w in ws], ws


def round_tail(provider, colors_ext, tail_wl, tail_ids, kind="bitset"):
    """The serial tail's coloring of ``tail_wl``, by dependency rounds.

    Bit-identical to ``serial_tail_step(provider.row1, colors_ext,
    tail_wl, kind)`` for a provider whose rows are symmetric with
    multiplicity (``v`` lies in ``u``'s row as often as ``u`` in ``v``'s):
    every FirstFit ``kind`` returns the smallest free color, and a
    frontier vertex reads exactly the colors the sequential loop would
    show it (see the comment above ``ROUND_CAP_FLOOR``).  ``tail_ids``
    holds the real entries of ``tail_wl`` on the host; each round
    dispatches every class's frontier (``_tail_classes``) at a power-of-two
    lane count of at least ``ROUND_CAP_FLOOR``.  One count readback per
    round, as a super-step.  Returns ``(colors_ext, rounds)``.
    """
    n = colors_ext.shape[0] - 1
    members, widths = _tail_classes(provider, np.asarray(tail_ids, np.int32))
    padded = []
    for m in members:
        buf = np.full(max(ROUND_CAP_FLOOR, next_pow2(m.size)), n, np.int32)
        buf[:m.size] = m
        padded.append(jnp.asarray(buf))
    widths = tuple(widths)
    # count along C where the classes' tiles would gather more lanes
    by_entries = isinstance(provider, DeviceCSR) and sum(
        int(p.shape[0]) * w for p, w in zip(padded, widths)
    ) > provider.col_padded.shape[0]
    state, cnt, cls = provider_tail_setup(provider, colors_ext, tail_wl,
                                          tuple(padded), widths=widths,
                                          by_entries=by_entries)
    todo = sum(int(m.size) for m in members)
    rounds = 0
    while todo:
        csum, counts = provider_tail_frontier(state, cnt, cls,
                                              classes=len(members))
        counts = np.asarray(counts).tolist()
        if not any(counts):
            raise ValueError(
                f"serial tail: {todo} vertices wait on predecessors that "
                "never take a color; the provider's rows are not symmetric")
        for k, c in enumerate(counts):
            if c:
                state, cnt = provider_tail_round(
                    provider, state, cnt, csum, k,
                    cap=max(ROUND_CAP_FLOOR, next_pow2(c)), width=widths[k],
                    kind=kind)
        todo -= sum(counts)
        rounds += 1
    return state, rounds


def _dispatch_tail(provider, colors_ext, wl, tail_ids, *, kind, use_kernel,
                   width):
    """Run the serial tail over ``wl`` (``order_tail`` order; ``tail_ids``
    its real entries on the host): ``(colors_ext, rounds)``.

    ``use_kernel="csr"`` with a ``DeviceCSR`` provider runs the §18 grid=1
    sequential kernel (one dispatch, live aliased color state, one step
    per vertex); every other configuration runs ``round_tail``.  Both give
    ``serial_tail_step``'s sequential greedy bit for bit: every FirstFit
    ``kind`` returns the smallest free color, and a round colors exactly
    the vertices whose predecessors in the order are all colored.
    """
    if use_kernel == "csr" and isinstance(provider, DeviceCSR):
        from repro.kernels.superstep.csr_kernel import serial_tail_csr_tpu

        return serial_tail_csr_tpu(
            provider.row_starts, provider.col_padded, provider.deg_ext,
            colors_ext, wl, width), len(tail_ids)
    return round_tail(provider, colors_ext, wl, tail_ids, kind)


def _tiled_superstep(provider, deg_ext, colors_ext, wls, *, widths, heuristic,
                     kind, use_kernel, chunks, pack_degrees=False):
    """One degree-tiled super-step: every class sub-step in one computation.

    Classes gather at their own tile widths but all speculate against the
    iteration-start snapshot (writes are disjoint), so the result is
    bit-identical to a single full-width step over the union worklist.
    """
    snapshot = colors_ext
    K = len(wls)
    new_wls, counts = [], []
    for k in range(K):
        colors_ext, wl_k, cnt_k = ragged_superstep(
            lambda ids, w=widths[k]: provider.rows(ids, w),
            deg_ext, colors_ext, wls[k],
            heuristic=heuristic, kind=kind, use_kernel=use_kernel,
            coarsen=chunks[k],
            colors_read=None if K == 1 else snapshot,
            pack_degrees=pack_degrees,
            provider=provider, width=widths[k],
        )
        new_wls.append(wl_k)
        counts.append(cnt_k)
    return colors_ext, tuple(new_wls), tuple(counts)


provider_tiled_superstep = partial(
    jax.jit, static_argnames=("widths", "heuristic", "kind", "use_kernel",
                              "chunks", "pack_degrees")
)(_tiled_superstep)


# --------------------------------------------------------------------------
# the ragged engine driver (degree-tiled dispatch + adaptive tail)
# --------------------------------------------------------------------------

def run_ragged_engine(
    *,
    n: int,
    provider,
    deg_ext,
    classes: list,
    tile_widths: list,
    acc_widths: list,
    tail_width: int,
    mode: str = "workefficient",
    heuristic: str = "degree",
    kind: str = "bitset",
    use_kernel: bool = False,
    coarsen: int = 1,
    coarsen_lanes: int | None = None,
    tail_enabled: bool = True,
    tail_threshold: int = 0,
    max_iters: int,
    algorithm: str = "data_driven_sgr",
    pack_degrees: bool = False,
    colors_init=None,
    stall_serializes_all: bool = True,
    class_counts=None,
    trace=False,
) -> ColoringResult:
    """Drive the rotated super-step to convergence over degree-tiled classes.

    ``classes`` partitions the vertices (wide-first order); class ``k``'s
    worklist gathers ``provider.rows(ids, tile_widths[k])`` tiles, and
    ``padded_work`` charges ``lanes × acc_widths[k]`` gather cells.  When the
    total live count drops to ``tail_threshold`` — or the worklist *stalls*
    (a post-bootstrap step retires under 1/STALL_DEN of it, the signature of
    a cascading grid/circuit graph) — the remaining entries are handed to the
    serial tail: ``serial_tail_step``'s largest-degree-first sequential
    greedy, computed by ``round_tail`` one dependency round at a time (a
    vertex takes its color once every earlier neighbor in the order has
    one, so the colors are the sequential loop's bit for bit).  ``mode`` picks
    the host-loop (``workefficient``) or single-device-program (``fused``)
    realization of the *same* schedule — colors are bit-identical.

    ``colors_init`` warm-starts the engine (§14 incremental recoloring): a
    pre-colored ``(n + 1,)`` extended array whose non-worklist entries are
    FROZEN snapshot context — ``classes`` then need not partition all
    vertices, only the live frontier, and the work accounting charges that
    frontier (not n).  ``stall_serializes_all=False`` keeps the stall tail's
    scope to the live worklist (the cold default discards the speculation
    and re-greedies the whole graph, which would turn a frontier-sized
    recoloring into an O(n) one).  ``class_counts`` gives each class's TRUE
    live count when its worklist buffer carries trailing sentinel padding
    (callers pad to a power of two so jit cache keys repeat across calls);
    sentinel lanes are inert everywhere, so only the accounting and the
    tail/stall thresholds need the honest numbers.

    ``trace`` (§16) records one telemetry row per super-step into a bounded
    ring (``True`` = default capacity, an int = explicit capacity) and
    attaches the assembled ``repro.obs.RunTrace`` to the result.  The knob
    is static: ``trace=False`` dispatches the exact pre-§16 programs, so
    untraced runs stay bit-identical and pay nothing.
    """
    if pack_degrees and not _packed_gather_ok(tail_width):
        # §17 capacity guard: the packed color|deg<<16 word would overflow
        # int32 past deg 2^15 — silent color corruption, so refuse loudly
        from repro.errors import CapacityError
        from repro.ingest import PACKED_GATHER_MAX_DEG

        raise CapacityError(
            f"pack_degrees=True with tail_width={tail_width}: degrees must "
            f"stay < {PACKED_GATHER_MAX_DEG} to fit the packed gather word "
            "(color | deg << 16, int32); rerun with pack_degrees=False")
    caps0 = [int(c.shape[0]) for c in classes]
    counts_init = (caps0 if class_counts is None
                   else [int(c) for c in class_counts])
    boot_iters = 0
    if colors_init is not None:
        colors_ext = jnp.asarray(colors_init, dtype=jnp.int32)
    else:
        colors_ext = jnp.zeros((n + 1,), dtype=jnp.int32)
        # Bootstrap identity: with an unchunked worklist the first rotated
        # step FirstFits every vertex against an all-zero tile — everyone
        # takes color 1 and the worklist is unchanged.  Materialize that
        # constant instead of dispatching a full-width gather for it.  (Never
        # valid on a warm start: tiles read frozen colors, not zeros.)
        skip_bootstrap = coarsen <= 1 and (
            coarsen_lanes is None or coarsen_lanes >= max(caps0, default=1))
        if skip_bootstrap and max_iters >= 1:
            colors_ext = jnp.where(
                jnp.arange(n + 1, dtype=jnp.int32) < n, 1, 0
            ).astype(jnp.int32)
            boot_iters = 1

    trace_cap = resolve_trace_cap(trace, max_iters)
    trace_label = f"{algorithm}:{mode}"
    if mode == "fused":
        return _run_ragged_fused(
            n, provider, deg_ext, classes, tile_widths, acc_widths,
            tail_width, colors_ext, boot_iters, heuristic, kind, use_kernel,
            coarsen, coarsen_lanes, tail_enabled, tail_threshold, max_iters,
            algorithm, pack_degrees, counts_init, stall_serializes_all,
            trace_cap=trace_cap,
        )
    if mode != "workefficient":
        raise ValueError(f"unknown mode {mode!r}")

    K = len(classes)
    caps = caps0
    wls = [jnp.asarray(c) for c in classes]
    counts = list(counts_init)
    iters = boot_iters
    work = n if boot_iters else 0
    padded = 0
    cells_k = [0] * K  # per-class gather cells (partitions ``padded``)
    total = sum(counts)
    prev = total
    stalled = False
    rows = []  # (§16) one telemetry row per super-step when tracing
    if trace_cap and boot_iters:
        rows.append((n, 0, n, 1, 0, 0, 0, 0))
    with span("superstep_loop", mode=mode):
        while total > 0 and iters < max_iters:
            if tail_enabled and total <= tail_threshold:
                break
            if tail_enabled and _stalled(iters, total, prev):
                stalled = True
                break
            prev = total
            sliced, chunk_l = [], []
            step_cells = 0
            for k in range(K):
                cap = min(next_pow2(max(counts[k], 1)), caps[k])
                sliced.append(wls[k][:cap])
                chunk_l.append(max(1, math.ceil(cap / coarsen_lanes))
                               if coarsen_lanes else coarsen)
                work += counts[k]
                if counts[k]:
                    padded += cap * acc_widths[k]
                    cells_k[k] += cap * acc_widths[k]
                    step_cells += cap * acc_widths[k]
            # the count reads wait for the device: the span holds the step
            with span("superstep", step=iters, live=total, cells=step_cells):
                colors_ext, new_wls, cnts = provider_tiled_superstep(
                    provider, deg_ext, colors_ext, tuple(sliced),
                    widths=tuple(tile_widths), heuristic=heuristic, kind=kind,
                    use_kernel=use_kernel, chunks=tuple(chunk_l),
                    pack_degrees=pack_degrees,
                )
                counts = [int(c) for c in cnts]
            wls = list(new_wls)
            iters += 1
            new_total = sum(counts)
            if trace_cap:
                rows.append((total, total - new_total, new_total,
                             int(jnp.max(colors_ext)), step_cells, 0, 0, 0))
            total = new_total
    converged = total == 0
    tail_cells = 0
    if total > 0 and iters < max_iters and tail_enabled:
        if stalled and stall_serializes_all:
            # speculation failed to make progress — discard it and run one
            # clean largest-degree-first sequential greedy over the graph
            tail_ids = tail_np = np.arange(n, dtype=np.int32)
        else:
            tail_ids = np.concatenate(
                [np.asarray(wls[k][:counts[k]]) for k in range(K) if counts[k]]
            )
            tail_np = np.full(min(next_pow2(total), n), n, np.int32)
            tail_np[:total] = tail_ids
        with span("serial_tail", live=total, stalled=stalled,
                  vertices=int(tail_np.shape[0])) as sp:
            tail_wl = order_tail(jnp.asarray(tail_np), deg_ext)
            colors_ext, sp.meta["rounds"] = _dispatch_tail(
                provider, colors_ext, tail_wl, tail_ids, kind=kind,
                use_kernel=use_kernel, width=tail_width)
            # the readback below waits for this buffer anyway: the span
            # ends when the device is done with the tail
            colors_ext.block_until_ready()
        work += n if stalled and stall_serializes_all else total
        tail_cells = int(tail_wl.shape[0]) * tail_width
        padded += tail_cells
        iters += 1
        converged = True
        if trace_cap:
            # the tail drains the LIVE worklist (total entries); a
            # stall-serialization additionally re-greedies settled vertices,
            # which shows up in ``cells``/work, not in worklist membership
            rows.append((total, total, 0, int(jnp.max(colors_ext)),
                         tail_cells, 1, 0, 0))
    result = ColoringResult(
        np.asarray(colors_ext[:n]), iters, work, padded, converged,
        algorithm=algorithm,
        class_cells=_class_cells(acc_widths, cells_k, tail_width, tail_cells),
    )
    if trace_cap:
        result.trace = assemble_trace(rows, iters, trace_cap, trace_label)
    return result


@partial(jax.jit, static_argnames=("tile_widths", "heuristic", "kind",
                                   "use_kernel", "chunks", "tail_enabled",
                                   "max_iters", "boot_iters", "pack_degrees",
                                   "trace_cap", "cells_per_step"))
def _fused_spec_loop(provider, deg_ext, colors_ext, wls, counts, thr, *,
                     tile_widths, heuristic, kind, use_kernel, chunks,
                     tail_enabled, max_iters, boot_iters=0,
                     pack_degrees=False, prev0=None, trace_cap=0,
                     cells_per_step=0):
    """The speculative phase as one ``lax.while_loop`` device program.

    ``trace_cap > 0`` (§16, a STATIC knob) threads a pre-allocated
    ``(trace_cap, NF)`` int32 trace ring through the carry and records one
    row per super-step at ``step % trace_cap``; with the default 0 the
    carry and the compiled program are exactly the pre-§16 ones, so the
    untraced path stays bit-identical and pays nothing.
    """
    n = colors_ext.shape[0] - 1
    K = len(wls)

    def total_of(counts):
        return sum(counts, jnp.int32(0))

    def cond(state):
        counts, it, prev = state[2], state[3], state[5]
        total = total_of(counts)
        go = (total > 0) & (it < max_iters)
        if tail_enabled:
            go &= (total > thr) & ~_stalled(it, total, prev)
        return go

    def body(state):
        colors_ext, wls, counts, it, work = state[:5]
        prev = total_of(counts)
        colors_ext, new_wls, new_counts = _tiled_superstep(
            provider, deg_ext, colors_ext, wls,
            widths=tile_widths, heuristic=heuristic, kind=kind,
            use_kernel=use_kernel, chunks=chunks, pack_degrees=pack_degrees,
        )
        total = total_of(new_counts)
        out = (colors_ext, new_wls, new_counts, it + 1, work + total, prev)
        if trace_cap:
            z = jnp.int32(0)
            row = jnp.stack([prev, prev - total, total, jnp.max(colors_ext),
                             jnp.int32(cells_per_step), z, z, z])
            idx = lax.rem(it - boot_iters, jnp.int32(trace_cap))
            out = out + (state[6].at[idx].set(row),)
        return out

    state = (colors_ext, wls, counts, jnp.int32(boot_iters), jnp.int32(0),
             jnp.int32(n if prev0 is None else prev0))
    if trace_cap:
        state = state + (ring_init(trace_cap),)
    return lax.while_loop(cond, body, state)


def _run_ragged_fused(
    n, provider, deg_ext, classes, tile_widths, acc_widths, tail_width,
    colors_ext, boot_iters, heuristic, kind, use_kernel, coarsen,
    coarsen_lanes, tail_enabled, tail_threshold, max_iters, algorithm,
    pack_degrees=False, counts_init=None, stall_serializes_all=True,
    trace_cap=0,
):
    K = len(classes)
    caps = [int(c.shape[0]) for c in classes]
    # cold runs partition all n vertices with exact-length worklists, so
    # init_total == n there; warm starts (§14) pass the true live counts of
    # their sentinel-padded frontier buffers and charge those instead
    counts_init = caps if counts_init is None else counts_init
    init_total = sum(counts_init)
    chunks = [coarsen] * K
    if coarsen_lanes:
        chunks = [max(1, math.ceil(c / coarsen_lanes)) for c in caps]
    wls0 = tuple(jnp.asarray(c) for c in classes)
    counts0 = tuple(jnp.int32(c) for c in counts_init)
    cells_per_step = sum(c * w for c, w in zip(caps, acc_widths))
    with span("superstep_loop", mode="fused"):
        out = _fused_spec_loop(
            provider, deg_ext, colors_ext, wls0, counts0,
            jnp.int32(tail_threshold),
            tile_widths=tuple(tile_widths), heuristic=heuristic, kind=kind,
            use_kernel=use_kernel, chunks=tuple(chunks),
            tail_enabled=tail_enabled, max_iters=max_iters,
            boot_iters=boot_iters, pack_degrees=pack_degrees,
            prev0=None if init_total == n else jnp.int32(init_total),
            trace_cap=trace_cap, cells_per_step=cells_per_step,
        )
    colors_ext, wls, counts, it, work, prev = out[:6]
    total = int(sum(int(c) for c in counts))
    iters = int(it)
    work_items = int(work) + init_total
    spec_steps = iters - boot_iters
    cells_k = [spec_steps * c * w for c, w in zip(caps, acc_widths)]
    padded = sum(cells_k)
    converged = total == 0
    tail_cells = 0
    rows = []
    if trace_cap:
        if boot_iters:
            rows.append((n, 0, n, 1, 0, 0, 0, 0))
        rows.extend(tuple(int(v) for v in r)
                    for r in ring_rows(np.asarray(out[6]), spec_steps))
    if total > 0 and iters < max_iters and tail_enabled:
        stalled = total > tail_threshold and bool(
            _stalled(iters, total, int(prev)))
        with span("serial_tail", live=total, stalled=stalled) as sp:
            if stalled and stall_serializes_all:
                tail_wl = order_tail(jnp.arange(n, dtype=jnp.int32), deg_ext)
                tail_ids = np.arange(n, dtype=np.int32)
            else:
                combined = jnp.concatenate(list(wls)) if K > 1 else wls[0]
                tail_wl = order_tail(combined, deg_ext)
                tail_ids = np.concatenate(
                    [np.asarray(w[:int(c)]) for w, c in zip(wls, counts)])
            sp.meta["vertices"] = int(tail_wl.shape[0])
            colors_ext, sp.meta["rounds"] = _dispatch_tail(
                provider, colors_ext, tail_wl, tail_ids, kind=kind,
                use_kernel=use_kernel, width=tail_width)
            colors_ext.block_until_ready()  # the readback waits for it anyway
        work_items += n if stalled and stall_serializes_all else total
        tail_cells = int(tail_wl.shape[0]) * tail_width
        padded += tail_cells
        iters += 1
        converged = True
        if trace_cap:
            rows.append((total, total, 0, int(jnp.max(colors_ext)),
                         tail_cells, 1, 0, 0))
    result = ColoringResult(
        np.asarray(colors_ext[:n]), iters, work_items, padded, converged,
        algorithm=algorithm,
        class_cells=_class_cells(acc_widths, cells_k, tail_width, tail_cells),
    )
    if trace_cap:
        result.trace = assemble_trace(rows, iters, trace_cap,
                                      f"{algorithm}:fused")
    return result


# --------------------------------------------------------------------------
# generic drivers for the classic step (shared with topo.py / repro.d2)
# --------------------------------------------------------------------------
# The two driver loops are generic over the super-step: ``step(colors_ext,
# wl) -> (colors_ext, wl, count)``.  The classic engine instantiates them
# with ``sgr_step``; legacy distance-2 callers reuse them with the two-hop
# super-step instead of copying the scaffolding.

def run_fused_loop(step, colors_ext, wl0, count0, max_iters: int,
                   trace_cap: int = 0, cells_per_step: int = 0):
    """The whole coloring as ONE jitted ``lax.while_loop`` device program.

    Returns ``(colors_ext, wl, count, iters, work)`` where ``work`` is the
    sum of post-step live counts (the first full-capacity step is charged by
    the caller, matching the paper's work accounting).  With ``trace_cap >
    0`` (§16) a ``(trace_cap, NF)`` trace ring rides the carry — one row per
    step at ``step % trace_cap`` — and is returned as a sixth element; the
    default 0 compiles the pre-§16 five-element program unchanged.
    """

    @partial(jax.jit, static_argnames=())
    def run(colors_ext, wl, count):
        def cond(state):
            count, it = state[2], state[3]
            return (count > 0) & (it < max_iters)

        def body(state):
            colors_ext, wl, count, it, work = state[:5]
            prev = count
            colors_ext, wl, count = step(colors_ext, wl)
            out = (colors_ext, wl, count, it + 1, work + count)
            if trace_cap:
                z = jnp.int32(0)
                row = jnp.stack([prev, prev - count, count,
                                 jnp.max(colors_ext),
                                 jnp.int32(cells_per_step), z, z, z])
                idx = lax.rem(it, jnp.int32(trace_cap))
                out = out + (state[5].at[idx].set(row),)
            return out

        state = (colors_ext, wl, count, jnp.int32(0), jnp.int32(0))
        if trace_cap:
            state = state + (ring_init(trace_cap),)
        return lax.while_loop(cond, body, state)

    return run(colors_ext, wl0, jnp.int32(count0))


def fused_result(colors_ext, n: int, count, it, work, width: int = 1,
                 algorithm: str = "data_driven_sgr") -> ColoringResult:
    """Shared result assembly for fused drivers (paper work accounting).

    Every super-step dispatches full capacity, so ``padded_work`` is
    ``iters * n * width`` gather cells and the first step's n live items are
    charged on top of the post-step counts accumulated in ``work``.
    """
    iters = int(it)
    return ColoringResult(
        np.asarray(colors_ext[:n]),
        iters,
        int(work) + n,
        iters * n * width,
        converged=int(count) == 0,
        algorithm=algorithm,
    )


def run_workefficient_loop(step, colors_ext, wl0, count0: int, max_iters: int):
    """Host loop re-slicing the worklist to the next pow2 of the live count.

    Single-class variant of the paper's work-efficiency argument (the
    class-tiled loop lives in ``run_ragged_engine``).  Returns
    ``(colors_ext, iters, work, padded, converged)``; ``padded`` counts
    dispatched lanes (multiply by the tile width for gather cells).
    """
    wl, count = wl0, int(count0)
    iters = work = padded = 0
    while count > 0 and iters < max_iters:
        cap = min(next_pow2(count), wl.shape[0])
        colors_ext, wl, cnt = step(colors_ext, wl[:cap])
        work += count
        padded += cap
        count = int(cnt)
        iters += 1
    return colors_ext, iters, work, padded, count == 0


def _prepare(g: CSRGraph, buckets):
    """Device arrays + per-bucket (ids, sliced adjacency) covering each class."""
    adj_np = g.padded_adjacency()
    deg_ext = jnp.asarray(
        np.concatenate([g.degrees, np.zeros(1, np.int32)]).astype(np.int32)
    )
    if buckets:
        classes = g.degree_buckets(buckets)
        widths = []
        bounds = list(buckets) + [max(g.max_degree, 1)]
        for hi in bounds:
            widths.append(min(max(hi, 1), adj_np.shape[1]))
        # process large-degree classes first (aligns with the degree heuristic)
        order = np.argsort([-w for w in widths], kind="stable")
        classes = [classes[i] for i in order]
        widths = [widths[i] for i in order]
    else:
        classes = [np.arange(g.n, dtype=np.int32)]
        widths = [adj_np.shape[1]]
    adjs = [jnp.asarray(adj_np[:, :w]) for w in widths]
    return adjs, deg_ext, classes


def _resolve_classes(degrees: np.ndarray, buckets, tiling):
    """(classes, widths) for the degree-tiled dispatch, wide-first order.

    Explicit ``buckets`` win; otherwise ``tiling`` is ``"auto"`` (log-spaced
    thresholds from the degree histogram), an explicit threshold tuple, or
    ``None``/``()`` for a single full-width class.  Takes the raw degree
    histogram of the GATHERED side (the original graph's, G²'s, or a
    conflict graph's — shared with ``repro.d2``); degree-0 vertices join the
    narrowest class, empty classes are dropped.
    """
    degrees = np.asarray(degrees)
    n = int(degrees.size)
    dmax = max(int(degrees.max(initial=0)), 1)
    if buckets:
        thresholds = tuple(buckets)
    elif tiling == "auto":
        thresholds = auto_tile_thresholds(degrees)
    elif not tiling:
        thresholds = ()
    else:
        thresholds = tuple(tiling)
    if not thresholds:
        return [np.arange(n, dtype=np.int32)], [dmax]
    bounds = list(thresholds) + [dmax]
    widths = [min(max(b, 1), dmax) for b in bounds]
    classes, lo = [], 0
    for hi in bounds:
        classes.append(
            np.where((degrees > lo) & (degrees <= hi))[0].astype(np.int32))
        lo = hi
    zero = np.where(degrees == 0)[0].astype(np.int32)
    if zero.size:  # degree-0 vertices take color 1 trivially: narrowest class
        classes[0] = np.concatenate([zero, classes[0]])
    order = np.argsort([-w for w in widths], kind="stable")
    pairs = [(classes[i], widths[i]) for i in order if classes[i].size]
    if not pairs:
        return [np.arange(n, dtype=np.int32)], [dmax]
    return [p[0] for p in pairs], [p[1] for p in pairs]


def _graph_device_cache(g, key: str, build):
    """Memoize device-side views on the (frozen) host graph object.

    CSRGraph is immutable, so its device transfers (CSR arrays, dense
    adjacency, extended degrees) are pure functions of the object — cache
    them on the instance so repeated ``color()`` calls skip the host→device
    uploads.  ``object.__setattr__`` bypasses the frozen-dataclass guard.
    """
    cache = getattr(g, "_device_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(g, "_device_cache", cache)
    if key not in cache:
        cache[key] = build()
    return cache[key]


@register("data_driven")
def color_data_driven(
    g: CSRGraph,
    *,
    heuristic: str = "degree",
    firstfit: str = "bitset",
    use_kernel: bool = False,
    coarsen_ff: int = 1,
    coarsen_cr: int = 1,
    coarsen_lanes: int | None = None,
    buckets: tuple[int, ...] = (),
    mode: str = "workefficient",
    max_iters: int | None = None,
    reuse_rows: bool = False,
    engine: str = "ragged",
    tiling="auto",
    tail_serial="auto",
    devices=None,
    backend: str | None = None,
    trace=False,
) -> ColoringResult:
    """Color ``g`` with the paper's optimized data-driven SGR algorithm.

    ``backend`` picks the super-step implementation (DESIGN.md §15):
    ``"pallas"`` routes every degree-class tile through the fused Pallas
    kernel (``kernels/superstep``; ``interpret=True`` off-TPU), ``"jax"``
    forces the pure-JAX formulation, ``"auto"`` resolves to ``"jax"``
    (``kernels/dispatch.py``), and ``None`` defers to the legacy
    ``use_kernel`` knob.  Colors are
    bit-identical across backends (tested in ``tests/test_differential.py``);
    the multi-device sharded engine always runs pure-JAX (automatic
    fallback — its ``shard_map`` body cannot host the kernel).

    ``engine`` picks the execution engine (see the module docstring):
    ``ragged`` (CSR-native rotated super-step, the default), ``padded``
    (same schedule over the dense padded-adjacency table — bit-identical
    colors), ``classic`` (the two-phase baseline), or ``sharded`` (the §13
    multi-device engine over ``devices`` — defaults to every available
    device, falls back to ``ragged`` when only one is present; colors are
    bit-identical either way, and ``mode`` is pinned to the fused
    schedule/accounting so results never depend on the device count).
    ``tiling`` controls the degree-tiled
    dispatch (``"auto"``, explicit thresholds, or ``None``) and
    ``tail_serial`` the adaptive tail-serialization (``"auto"``, an
    explicit live-count threshold, or ``None`` to disable).

    ``coarsen_lanes`` models the paper's thread-coarsening launch config
    (nSM x max_blocks x 128 threads): the speculative phase is chunked so at
    most ``coarsen_lanes`` vertices speculate concurrently; later chunks
    observe earlier chunks' colors, exactly like CUDA blocks scheduled in
    waves.  Overrides ``coarsen_ff`` when set.

    ``trace`` (§16) records per-super-step telemetry and host phase spans
    into ``result.trace`` (a ``repro.obs.RunTrace``).  Static knob: the
    default ``False`` dispatches the identical device programs, so untraced
    results stay bit-identical and free of overhead.
    """
    from repro.kernels.dispatch import kernel_mode, resolve_backend

    n = g.n
    if n == 0:
        resolve_backend(backend, use_kernel)  # validate even on the no-op
        result = ColoringResult(np.zeros(0, np.int32), 0, 0, 0, True)
        if trace:
            result.trace = empty_trace("data_driven_sgr")
        return result
    max_iters = max_iters or n + 1

    def run(engine=engine, mode=mode, use_kernel=use_kernel):
        if engine == "classic":
            # the classic engine's two-phase kernels take dense tiles only;
            # pallas-csr degrades to the gathered kernel (bit-identical)
            use_kernel = resolve_backend(backend, use_kernel) in (
                "pallas", "pallas-csr")
            return _color_classic(
                g, heuristic, firstfit, use_kernel, coarsen_ff, coarsen_cr,
                coarsen_lanes, buckets, mode, max_iters, reuse_rows,
                trace_cap=resolve_trace_cap(trace, max_iters),
            )
        if engine == "sharded":
            # validate BEFORE the one-device fallback so the accepted option
            # surface never depends on how many devices happen to be present
            if use_kernel:
                raise ValueError(
                    "engine='sharded' does not support use_kernel=True")
            if coarsen_ff != 1 or coarsen_cr != 1 or coarsen_lanes:
                raise ValueError(
                    "engine='sharded' runs the uncoarsened (coarsen=1) "
                    "schedule; coarsen_ff/coarsen_cr/coarsen_lanes are not "
                    "supported")
            devs = list(devices) if devices is not None else jax.devices()
            if len(devs) > 1:
                # §15 fallback: the shard_map body stays pure-JAX; a pallas
                # request degrades to wall-clock only (colors bit-identical)
                resolve_backend(backend)
                from repro.core.distributed import color_distributed

                return color_distributed(
                    g, devices=devs, heuristic=heuristic, firstfit=firstfit,
                    buckets=buckets, tiling=tiling, tail_serial=tail_serial,
                    max_iters=max_iters, trace=trace,
                )
            # one device: the sharded schedule IS the ragged fused one — pin
            # mode so colors AND accounting are device-count-independent
            engine, mode = "ragged", "fused"
        use_kernel = kernel_mode(resolve_backend(backend, use_kernel))
        if engine not in ("ragged", "padded"):
            raise ValueError(
                f"unknown engine {engine!r}; options: ragged, padded, "
                f"classic, sharded"
            )

        with span("partition_plan"):
            classes, widths = _resolve_classes(g.degrees, buckets, tiling)
        dmax = max(g.max_degree, 1)
        with span("csr_build", engine=engine):
            deg_ext = _graph_device_cache(g, "deg_ext", lambda: jnp.asarray(
                np.concatenate(
                    [g.degrees, np.zeros(1, np.int32)]).astype(np.int32)
            ))
            if engine == "ragged":
                provider = _graph_device_cache(
                    g, "dcsr", lambda: DeviceCSR.from_csr(g))
                tile_widths = widths
                acc_widths = widths
            else:
                provider = _graph_device_cache(g, "dense", lambda: DenseRows(
                    jnp.asarray(g.padded_adjacency())))
                tile_widths = [None] * len(widths)
                acc_widths = [dmax] * len(widths)
        tail_enabled, thr = resolve_tail_threshold(tail_serial, n)
        return run_ragged_engine(
            n=n,
            provider=provider,
            deg_ext=deg_ext,
            classes=classes,
            tile_widths=tile_widths,
            acc_widths=acc_widths,
            tail_width=dmax,
            mode=mode,
            heuristic=heuristic,
            kind=firstfit,
            use_kernel=use_kernel,
            coarsen=max(int(coarsen_ff), int(coarsen_cr)),
            coarsen_lanes=coarsen_lanes,
            tail_enabled=tail_enabled,
            tail_threshold=thr,
            max_iters=max_iters,
            pack_degrees=_packed_gather_ok(dmax),
            trace=trace,
        )

    if not trace:
        return run()
    # trace=True opens its own span recorder so result.trace.spans carries
    # the phase breakdown even without a user recorder; an outer recorder
    # (repro.obs.recorder()) still observes every span — recorders nest
    with SpanRecorder() as rec:
        result = run()
    if result.trace is not None:
        result.trace.spans = rec.events
    return result


def _color_classic(
    g, heuristic, firstfit, use_kernel, coarsen_ff, coarsen_cr,
    coarsen_lanes, buckets, mode, max_iters, reuse_rows, trace_cap=0,
):
    """The pre-§12 two-phase engine (FirstFit kernel + ConflictResolve kernel)."""
    n = g.n
    adjs, deg_ext, classes = _prepare(g, buckets)
    colors_ext = jnp.zeros((n + 1,), dtype=jnp.int32)

    if mode == "fused":
        assert not buckets, "classic fused mode runs single-class (full-width) only"
        return _run_fused(
            g, adjs[0], deg_ext, colors_ext, heuristic, firstfit, coarsen_ff,
            coarsen_cr, use_kernel, max_iters, reuse_rows, trace_cap,
        )
    if mode != "workefficient":
        raise ValueError(f"unknown mode {mode!r}")

    widths = [int(a.shape[1]) for a in adjs]
    # per-class worklists (class membership is static: degrees never change)
    wls = [jnp.asarray(ids) for ids in classes]
    counts = [int(ids.shape[0]) for ids in classes]
    iters = work = padded = 0
    rows = []
    with span("superstep_loop", mode=mode):
        while sum(counts) > 0 and iters < max_iters:
            live_in = sum(counts)
            step_cells = 0
            new_wls, new_counts = [], []
            for k, (wl, count, adj_k) in enumerate(zip(wls, counts, adjs)):
                if count == 0:
                    new_wls.append(wl[:1])
                    new_counts.append(0)
                    continue
                cap = min(next_pow2(count), wl.shape[0])
                if coarsen_lanes:
                    coarsen_ff = max(1, math.ceil(cap / coarsen_lanes))
                colors_ext, wl_out, cnt = sgr_step(
                    adj_k,
                    deg_ext,
                    colors_ext,
                    wl[:cap],
                    heuristic=heuristic,
                    kind=firstfit,
                    coarsen_ff=coarsen_ff,
                    coarsen_cr=coarsen_cr,
                    use_kernel=use_kernel,
                    reuse_rows=reuse_rows,
                )
                work += count
                padded += cap * widths[k]
                step_cells += cap * widths[k]
                new_wls.append(wl_out)
                new_counts.append(int(cnt))
            wls, counts = new_wls, new_counts
            iters += 1
            if trace_cap:
                new_total = sum(counts)
                rows.append((live_in, live_in - new_total, new_total,
                             int(jnp.max(colors_ext)), step_cells, 0, 0, 0))

    colors = np.asarray(colors_ext[:n])
    result = ColoringResult(colors, iters, work, padded,
                            converged=sum(counts) == 0)
    if trace_cap:
        result.trace = assemble_trace(rows, iters, trace_cap,
                                      "classic:workefficient")
    return result


@register("fused")
def color_fused(g: CSRGraph, **opts) -> ColoringResult:
    """``data_driven`` with the whole coloring as one device program."""
    opts.pop("mode", None)
    return color_data_driven(g, mode="fused", **opts)


def _run_fused(
    g, adj, deg_ext, colors_ext, heuristic, kind, coarsen_ff, coarsen_cr,
    use_kernel, max_iters, reuse_rows=False, trace_cap=0,
):
    n = g.n
    step = partial(
        sgr_step,
        adj,
        deg_ext,
        heuristic=heuristic,
        kind=kind,
        coarsen_ff=coarsen_ff,
        coarsen_cr=coarsen_cr,
        use_kernel=use_kernel,
        reuse_rows=reuse_rows,
    )
    wl0 = jnp.arange(n, dtype=jnp.int32)
    width = int(adj.shape[1])
    with span("superstep_loop", mode="fused"):
        out = run_fused_loop(
            step, colors_ext, wl0, n, max_iters,
            trace_cap=trace_cap, cells_per_step=n * width,
        )
    colors_ext, _, count, it, work = out[:5]
    result = fused_result(colors_ext, n, count, it, work, width=width)
    if trace_cap:
        rows = ring_rows(np.asarray(out[5]), int(it))
        result.trace = assemble_trace(rows, int(it), trace_cap,
                                      "classic:fused")
    return result
