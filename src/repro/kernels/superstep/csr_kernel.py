"""Pallas TPU kernel: the CSR-resident fused super-step (DESIGN.md §18).

The gathered kernel (``kernel.py``) consumes dense ``(w, W)`` neighbor
tiles that ``core/coloring.py`` materializes in HBM first — every gather
cell is written by the host-side gather AND read back by the kernel, twice
the traffic the paper's memory-bound analysis (§3) budgets for.  This
variant eliminates the intermediate tile entirely: it takes the
``DeviceCSR`` arrays (row offsets ``R``, column ids ``C``) plus a packed
``color | degree << 16`` table and gathers each worklist row's neighbors
into VMEM *itself*, then runs ConflictResolve + bitset FirstFit from the
same registers and writes only ``(new_color, need)`` back.

Layout (``pltpu.PrefetchScalarGridSpec``):

* scalar prefetch — the compacted worklist ids ``wl (w,)`` and their
  pre-gathered row offsets ``starts (w,) = R[clip(wl, 0, n-1)]``; both are
  resident in SMEM before the grid runs, so the kernel can issue its
  per-row dynamic slices of ``C`` without a host round trip.
* ANY-space operands — ``C`` (``col_padded``, sentinel slack at the end so
  a full-width slice at the last row never reads out of bounds) and the
  ``(n + 1,)`` packed word table (slot ``n`` holds 0, keeping sentinel
  lanes inert exactly like the extended color array).
* per-block VMEM scratch — one ``(block_n, W)`` neighbor-id tile, loaded
  row-by-row with ``pl.ds`` and consumed vectorized.

Bit-identity: lanes past a row's degree are masked to the sentinel ``n``
(whose packed word is 0 → color 0, degree 0), which reproduces the exact
inputs ``DeviceCSR.gather_rows`` + the packed pure-JAX gather would feed
the gathered kernel; the conflict rule below is ``superstep_kernel``'s and
the bitset FirstFit is the shared ``kernels/bitset.py``.  ``interpret=True``
keeps the kernel testable on CPU CI.

The grid=1 sequential variant at the bottom fuses the §12 serial tail
on-device: clear the worklist's colors, then FirstFit each vertex in the
given (``order_tail``) order against the LIVE aliased color array — the
canonical sequential greedy ``serial_tail_step`` computes, as one kernel
instead of a ``fori_loop`` of per-vertex gather/scatter dispatches.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitset import first_free, forbidden_words, nwords_for
from repro.kernels.superstep.ops import _pick_block_n

__all__ = [
    "superstep_csr_kernel",
    "superstep_csr_pallas_call",
    "superstep_csr_tpu",
    "serial_tail_csr_kernel",
    "serial_tail_csr_pallas_call",
    "serial_tail_csr_tpu",
]


def superstep_csr_kernel(wl_ref, starts_ref, col_ref, packed_ref,
                         newc_ref, need_ref, nid_s, *,
                         block_n: int, W: int, nwords: int, n: int,
                         heuristic: str):
    i = pl.program_id(0)
    base = i * block_n

    # ---- the fused gather: one (block_n, W) neighbor-id tile into VMEM ----
    def load_row(r, _):
        nid_s[r, :] = col_ref[pl.ds(starts_ref[base + r], W)]
        return 0

    lax.fori_loop(0, block_n, load_row, 0)

    my_id = wl_ref[pl.ds(base, block_n)]          # (bn,) worklist ids (SMEM)
    mypk = packed_ref[my_id]                      # sentinel n -> word 0
    my_c = mypk & jnp.int32(0xFFFF)
    my_d = mypk >> 16
    lane = lax.broadcasted_iota(jnp.int32, (block_n, W), 1)
    # lanes past my degree read the NEXT row's entries in C — mask them to
    # the sentinel n, whose packed word is 0 (color 0 / degree 0, inert)
    nid = jnp.where(lane < my_d[:, None], nid_s[...], jnp.int32(n))
    npk = packed_ref[nid]                         # (bn, W) packed gather
    nc = npk & jnp.int32(0xFFFF)
    nd = npk >> 16

    # ---- identical arithmetic to superstep_kernel (bit-identity bar) ------
    my_id2 = my_id[:, None]
    my_c2 = my_c[:, None]
    same = (nc == my_c2) & (my_c2 > 0)
    if heuristic == "id":
        lose_lane = same & (my_id2 < nid)
    else:  # degree: larger degree keeps; tie -> smaller id keeps
        lose_lane = same & ((nd > my_d[:, None])
                            | ((nd == my_d[:, None]) & (nid < my_id2)))
    need = jnp.any(lose_lane, axis=1) | (my_c == 0)

    nc = jnp.where(same & ~lose_lane, 0, nc)
    words = forbidden_words(nc, jnp.zeros((block_n, nwords), jnp.uint32))
    ff = first_free(words, W)[:, 0]

    newc_ref[...] = jnp.where(need, ff, my_c).astype(jnp.int32)
    need_ref[...] = need.astype(jnp.int32)


def superstep_csr_pallas_call(w: int, W: int, block_n: int, n: int,
                              heuristic: str, interpret: bool):
    """Build the CSR-resident super-step call for a width-``W`` class.

    ``w`` must be a multiple of ``block_n`` (the wrapper pads the worklist
    with sentinels) — scalar-prefetch reads have no out-of-bounds block
    padding, unlike dense BlockSpec operands.
    """
    nwords = nwords_for(W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # wl, starts
        grid=(w // block_n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # col_padded
            pl.BlockSpec(memory_space=pl.ANY),  # packed color|deg table
        ],
        out_specs=(
            pl.BlockSpec((block_n,), lambda i, *_: (i,)),
            pl.BlockSpec((block_n,), lambda i, *_: (i,)),
        ),
        scratch_shapes=[pltpu.VMEM((block_n, W), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(
            superstep_csr_kernel, block_n=block_n, W=W, nwords=nwords,
            n=n, heuristic=heuristic,
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((w,), jnp.int32),
            jax.ShapeDtypeStruct((w,), jnp.int32),
        ),
        interpret=interpret,
    )


@partial(jax.jit,
         static_argnames=("W", "heuristic", "n", "block_n", "interpret"))
def _run_csr(row_starts, col_padded, packed, wl, *, W, heuristic, n,
             block_n, interpret):
    w = wl.shape[0]
    pad = (-w) % block_n
    if pad:
        wl = jnp.concatenate([wl, jnp.full((pad,), n, wl.dtype)])
    starts = row_starts[jnp.clip(wl, 0, max(n - 1, 0))]
    newc, need = superstep_csr_pallas_call(
        w + pad, W, block_n, n, heuristic, interpret
    )(wl, starts, col_padded, packed)
    return newc[:w], need[:w]


def superstep_csr_tpu(
    row_starts: jax.Array,
    col_padded: jax.Array,
    packed: jax.Array,
    wl: jax.Array,
    W: int,
    heuristic: str = "degree",
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused gather + conflict-check + FirstFit straight from CSR storage.

    ``row_starts``/``col_padded`` are the ``DeviceCSR`` arrays; ``packed``
    is the ``(n + 1,)`` ``color | degree << 16`` table (slot ``n`` = 0) and
    ``W`` the degree-class tile width.  Returns ``(new_colors, need)`` for
    the worklist ``wl`` — sentinel masking (``wl < n``) is the caller's
    job, matching ``superstep_tpu``.  Requires the packed-word capacity
    predicate (``repro.ingest.packed_gather_ok``); callers fall back to
    the gathered kernel when it fails.
    """
    w = wl.shape[0]
    n = row_starts.shape[0] - 1
    if w == 0 or n == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool)
    interpret = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    block_n = block_n or _pick_block_n(w, W, tiles=4)
    newc, need = _run_csr(
        row_starts, col_padded, packed.astype(jnp.int32),
        wl.astype(jnp.int32),
        W=int(W), heuristic=heuristic, n=n, block_n=block_n,
        interpret=interpret,
    )
    return newc, need.astype(bool)


# --------------------------------------------------------------------------
# the §12 serial tail as one grid=1 sequential kernel (on-device fusion)
# --------------------------------------------------------------------------

def serial_tail_csr_kernel(wl_ref, starts_ref, degs_ref, col_ref,
                           colors_in_ref, colors_ref, *,
                           T: int, W: int, n: int):
    """Clear-then-sequential-FirstFit over the LIVE aliased color array.

    Exactly ``serial_tail_step``'s schedule: worklist colors cleared up
    front (sentinel entries write the always-zero slot ``n``), then each
    vertex in worklist order refits to the smallest color its neighbors'
    *current* colors permit — later vertices observe earlier writes through
    the aliased output ref, so the pass is conflict-free by construction.
    The smallest-free-color scan is candidate-based (colors 1..W+1 vs the
    ≤W forbidden neighbor colors); every FirstFit ``kind`` computes that
    same value, so the kernel is bit-identical to all of them.
    """
    del colors_in_ref  # aliased to colors_ref; the live view is the output

    def clear(i, _):
        colors_ref[wl_ref[i]] = 0
        return 0

    lax.fori_loop(0, T, clear, 0)

    cand = lax.broadcasted_iota(jnp.int32, (W + 1, 1), 0)[:, 0] + 1

    def fit(i, _):
        v = wl_ref[i]
        raw = col_ref[pl.ds(starts_ref[i], W)]
        lane = lax.broadcasted_iota(jnp.int32, (W, 1), 0)[:, 0]
        nbr = jnp.where(lane < degs_ref[i], raw, jnp.int32(n))
        ncol = colors_ref[nbr]                   # LIVE state, earlier writes
        forbidden = jnp.any(cand[:, None] == ncol[None, :], axis=1)
        ff = jnp.min(jnp.where(forbidden, jnp.int32(W + 2), cand))
        colors_ref[v] = jnp.where(v < n, ff, 0).astype(jnp.int32)
        return 0

    lax.fori_loop(0, T, fit, 0)


def serial_tail_csr_pallas_call(T: int, W: int, n: int, interpret: bool):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # wl, starts, degs
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # col_padded
            pl.BlockSpec(memory_space=pl.ANY),  # colors_ext (aliased)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
    )
    return pl.pallas_call(
        functools.partial(serial_tail_csr_kernel, T=T, W=W, n=n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n + 1,), jnp.int32),
        # operand index counts the 3 scalar-prefetch args: colors_ext is #4
        input_output_aliases={4: 0},
        interpret=interpret,
    )


@partial(jax.jit, static_argnames=("W", "n", "interpret"))
def _run_tail(row_starts, col_padded, deg_ext, colors_ext, wl, *,
              W, n, interpret):
    starts = row_starts[jnp.clip(wl, 0, max(n - 1, 0))]
    degs = deg_ext[jnp.clip(wl, 0, n)]
    return serial_tail_csr_pallas_call(
        wl.shape[0], W, n, interpret
    )(wl, starts, degs, col_padded, colors_ext)


def serial_tail_csr_tpu(
    row_starts: jax.Array,
    col_padded: jax.Array,
    deg_ext: jax.Array,
    colors_ext: jax.Array,
    wl: jax.Array,
    W: int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """``serial_tail_step`` fused into one device kernel over CSR arrays.

    ``wl`` arrives pre-ordered (``order_tail``); ``W`` is the full gather
    width (>= every worklist degree).  Returns the updated ``colors_ext``.
    """
    n = row_starts.shape[0] - 1
    if wl.shape[0] == 0 or n == 0:
        return colors_ext
    interpret = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    return _run_tail(
        row_starts, col_padded, deg_ext, colors_ext.astype(jnp.int32),
        wl.astype(jnp.int32), W=int(W), n=n, interpret=interpret,
    )
