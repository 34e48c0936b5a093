"""Pallas TPU kernel: ConflictResolve detection (paper Alg. 5 + §3.2 heuristic).

One grid step decides, for ``block_n`` worklist vertices, whether each loses a
speculative conflict and must recolor.  The per-row scalars (vertex id, its
color, its degree) arrive packed in a ``(block_n, 3)`` int32 tile so every ref
is 2-D (TPU-native layout); the three ``(block_n, W)`` neighbor tiles (ids,
colors, degrees) stream HBM->VMEM via BlockSpec.  The loser rule is a pure
lane-wise compare + any-reduce — no gathers, no control flow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["conflict_kernel", "conflict_pallas_call", "COL_ID", "COL_COLOR", "COL_DEG"]

COL_ID, COL_COLOR, COL_DEG = 0, 1, 2


def conflict_kernel(me_ref, nid_ref, nc_ref, nd_ref, out_ref, *, heuristic: str):
    me = me_ref[...]                # (bn, 3): [id, color, degree]
    nid = nid_ref[...]              # (bn, W) neighbor ids (sentinel in pads)
    nc = nc_ref[...]                # (bn, W) neighbor colors (0 in pads)
    nd = nd_ref[...]                # (bn, W) neighbor degrees (0 in pads)

    my_id = me[:, COL_ID:COL_ID + 1]
    my_c = me[:, COL_COLOR:COL_COLOR + 1]
    my_d = me[:, COL_DEG:COL_DEG + 1]

    same = (nc == my_c) & (my_c > 0)
    if heuristic == "id":
        lose_lane = same & (my_id < nid)
    else:  # degree: larger degree keeps; tie -> smaller id keeps
        lose_lane = same & ((nd > my_d) | ((nd == my_d) & (nid < my_id)))
    out_ref[...] = jnp.any(lose_lane, axis=1, keepdims=True).astype(jnp.int32)


def conflict_pallas_call(w: int, W: int, block_n: int, heuristic: str, interpret: bool):
    """Build the conflict-detect pallas_call for a (w, W) tile -> (w, 1)."""
    grid = (pl.cdiv(w, block_n),)
    row_spec = pl.BlockSpec((block_n, W), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(conflict_kernel, heuristic=heuristic),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 3), lambda i: (i, 0)),
            row_spec,
            row_spec,
            row_spec,
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w, 1), jnp.int32),
        interpret=interpret,
    )
