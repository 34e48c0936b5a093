"""Pallas TPU kernel: bitset FirstFit over a TWO-LEVEL neighborhood (§11).

The distance-2 on-the-fly path gathers two color tiles per worklist vertex
— ``nc1`` (block_n, W1), the direct neighbors, and ``nc2`` (block_n, W2),
the two-hop neighbors — and the forbidden set is their UNION.  Building the
packed uint32 bit words from both tiles inside one kernel keeps the
combined forbidden set register-resident instead of materializing the
``(w, W1 + W2)`` concatenation in HBM, which is the whole point at two-hop
widths (W2 grows like W²).

Both tiles accumulate, and find-first-set runs, through the shared helpers
in ``kernels/bitset.py``, with the greedy bound W1+W2+1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitset import first_free, forbidden_words, nwords_for

__all__ = ["d2_firstfit_kernel", "d2_firstfit_pallas_call"]


def d2_firstfit_kernel(nc1_ref, nc2_ref, out_ref, *, nwords: int):
    nc1 = nc1_ref[...]  # (block_n, W1) int32 hop-1 colors; 0 = none
    nc2 = nc2_ref[...]  # (block_n, W2) int32 hop-2 colors; 0 = none
    block_n = nc1.shape[0]
    bound = nc1.shape[1] + nc2.shape[1]  # colors 1..bound can be forbidden

    words = jnp.zeros((block_n, nwords), jnp.uint32)
    words = forbidden_words(nc1, words)
    words = forbidden_words(nc2, words)
    out_ref[...] = first_free(words, bound)


def d2_firstfit_pallas_call(w: int, W1: int, W2: int, block_n: int,
                            interpret: bool):
    """Build the pallas_call for (w, W1) + (w, W2) color tiles -> (w, 1)."""
    return pl.pallas_call(
        functools.partial(d2_firstfit_kernel, nwords=nwords_for(W1 + W2)),
        grid=(pl.cdiv(w, block_n),),
        in_specs=[
            pl.BlockSpec((block_n, W1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, W2), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w, 1), jnp.int32),
        interpret=interpret,
    )
