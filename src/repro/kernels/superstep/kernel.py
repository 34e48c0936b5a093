"""Pallas TPU kernel: the fused super-step (DESIGN.md §12).

One grid step runs BOTH phases of the rotated SGR super-step for ``block_n``
worklist vertices over a single resident neighbor tile:

* **ConflictResolve** — does my current speculative color survive against my
  neighbors (paper Alg. 5 loser rule / §3.2 degree heuristic)?  A lane-wise
  compare + any-reduce over the tile.
* **FirstFit** — if it does not (or I am uncolored), the smallest permissible
  color from the same tile, via the §3.2 bitset: forbidden colors packed into
  uint32 words that live in VREGs for the whole kernel, find-first-set
  computed structurally (bit-iota + min-reduce, no ``__ffs`` on TPU); see
  ``kernels/bitset.py``.

The classic engine ran these as two kernels with two HBM round trips of the
``(w, W)`` neighbor tiles; here the tiles stream HBM->VMEM once and both
phases consume the same registers — the kernel-level half of the "one gather
per iteration" contract (`core/coloring.py` provides the gather-level half).

Layout matches the conflict kernel: per-row scalars packed as a
``(block_n, 3)`` int32 tile ``[id, color, degree]``; neighbor ids/colors/
degrees as three ``(block_n, W)`` tiles.  Outputs are ``(w, 1)`` columns:
the new color per row and an int32 "needs re-verification" flag (1 where
the row was recolored).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitset import first_free, forbidden_words, nwords_for

__all__ = ["superstep_kernel", "superstep_pallas_call",
           "COL_ID", "COL_COLOR", "COL_DEG"]

COL_ID, COL_COLOR, COL_DEG = 0, 1, 2


def superstep_kernel(me_ref, nid_ref, nc_ref, nd_ref, newc_ref, need_ref, *,
                     nwords: int, heuristic: str):
    me = me_ref[...]                # (bn, 3): [id, color, degree]
    nid = nid_ref[...]              # (bn, W) neighbor ids (sentinel in pads)
    nc = nc_ref[...]                # (bn, W) neighbor colors (0 in pads)
    nd = nd_ref[...]                # (bn, W) neighbor degrees (0 in pads)
    block_n, W = nc.shape

    my_id = me[:, COL_ID:COL_ID + 1]
    my_c = me[:, COL_COLOR:COL_COLOR + 1]
    my_d = me[:, COL_DEG:COL_DEG + 1]

    # ---- phase 1: conflict detection on the current speculative colors ----
    same = (nc == my_c) & (my_c > 0)
    if heuristic == "id":
        lose_lane = same & (my_id < nid)
    else:  # degree: larger degree keeps; tie -> smaller id keeps
        lose_lane = same & ((nd > my_d) | ((nd == my_d) & (nid < my_id)))
    need = jnp.any(lose_lane, axis=1, keepdims=True) | (my_c == 0)

    # ---- phase 2: bitset FirstFit from the SAME tile (words stay in VREGs) --
    # same-color lanes I beat are provably recoloring too — refit as if they
    # were already cleared (the classic engine's clear-then-refit dynamics)
    nc = jnp.where(same & ~lose_lane, 0, nc)
    words = forbidden_words(nc, jnp.zeros((block_n, nwords), jnp.uint32))
    ff = first_free(words, W)

    newc_ref[...] = jnp.where(need, ff, my_c).astype(jnp.int32)
    need_ref[...] = need.astype(jnp.int32)


def superstep_pallas_call(w: int, W: int, block_n: int, heuristic: str,
                          interpret: bool):
    """Build the fused super-step pallas_call for a (w, W) neighbor tile.

    Outputs are ``(w, 1)`` columns: a rank-1 ``(block_n,)`` block is refused
    by Mosaic unless ``block_n`` matches the 1-D HBM tiling (1024).
    """
    grid = (pl.cdiv(w, block_n),)
    row_spec = pl.BlockSpec((block_n, W), lambda i: (i, 0))
    col_spec = pl.BlockSpec((block_n, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(superstep_kernel, nwords=nwords_for(W),
                          heuristic=heuristic),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 3), lambda i: (i, 0)),
            row_spec,
            row_spec,
            row_spec,
        ],
        out_specs=(col_spec, col_spec),
        out_shape=(
            jax.ShapeDtypeStruct((w, 1), jnp.int32),
            jax.ShapeDtypeStruct((w, 1), jnp.int32),
        ),
        interpret=interpret,
    )
