"""Pallas TPU kernel: bitset FirstFit (paper §3.2 "Bitset Operation").

One grid step FirstFits ``block_n`` worklist vertices.  The forbidden-color
set lives as packed uint32 words in VMEM/VREGs — the TPU analogue of the
paper's register-resident bitmask — built by a vectorized fori-loop over the
padded neighbor lanes.  CUDA's ``__ffs`` has no TPU counterpart, so
find-first-set is computed structurally: expand each word against a 32-lane
bit iota, mask out positions beyond the greedy bound W+1, and take the min
position (``kernels/bitset.py``).

VMEM working set per grid step: the (block_n, W) neighbor-color tile plus
(block_n, nwords) bit words — ``block_n`` is chosen by ops.py so this stays
within a ~2 MiB budget, the thread-coarsening knob of DESIGN.md §3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitset import first_free, forbidden_words, nwords_for

__all__ = ["firstfit_kernel", "firstfit_pallas_call"]


def firstfit_kernel(nc_ref, out_ref, *, nwords: int):
    nc = nc_ref[...]  # (block_n, W) int32 neighbor colors; 0 = none
    block_n, W = nc.shape
    words = forbidden_words(nc, jnp.zeros((block_n, nwords), jnp.uint32))
    out_ref[...] = first_free(words, W)


def firstfit_pallas_call(w: int, W: int, block_n: int, interpret: bool):
    """Build the pallas_call for a (w, W) neighbor-color tile -> (w, 1)."""
    return pl.pallas_call(
        functools.partial(firstfit_kernel, nwords=nwords_for(W)),
        grid=(pl.cdiv(w, block_n),),
        in_specs=[pl.BlockSpec((block_n, W), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w, 1), jnp.int32),
        interpret=interpret,
    )
