"""Kernel-side bitset FirstFit, shared by the superstep, firstfit and d2 kernels.

The paper's §3.2 "Bitset Operation": the forbidden colors of ``block_n``
rows are packed into uint32 words that stay in VREGs, and the first free
color is found structurally (bit-iota expansion + min-reduce), since the
TPU has no ``__ffs``.

Both helpers are written in forms Mosaic lowers for the TPU:

* Lanes are visited by rotating the tile one lane per step (``pltpu.roll``)
  and reading the static lane 0.  A traced lane index (``tile[:, d]``)
  lowers to ``dynamic_slice``, which Mosaic does not implement.
* Reductions keep a trailing unit axis, so results are ``(block_n, 1)``
  columns that kernels store into 2-D output blocks.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

__all__ = ["nwords_for", "forbidden_words", "first_free"]


def nwords_for(bound: int) -> int:
    """uint32 words that hold bits for colors ``1..bound+1``."""
    return (bound + 1 + 31) // 32


def forbidden_words(nc, words):
    """OR the bit of every color in ``nc`` (``(block_n, W)``; 0 = none) into
    ``words`` (``(block_n, nwords)`` uint32).  Colors past the last word are
    ignored."""
    block_n, W = nc.shape
    word_iota = lax.broadcasted_iota(jnp.int32, words.shape, 1)

    def accumulate(_, carry):
        words, tile = carry
        idx = tile[:, :1] - 1                 # lane 0's color as a bit index
        valid = idx >= 0
        hit = word_iota == jnp.where(valid, idx >> 5, -1)
        bit = (jnp.where(valid, idx, 0) & 31).astype(jnp.uint32)
        words = words | jnp.where(hit, jnp.uint32(1) << bit, jnp.uint32(0))
        return words, pltpu.roll(tile, W - 1, 1)   # next lane into lane 0

    return lax.fori_loop(0, W, accumulate, (words, nc))[0]


def first_free(words, bound: int):
    """Smallest color in ``[1, bound + 1]`` whose bit is clear, as a
    ``(block_n, 1)`` int32 column."""
    block_n, nwords = words.shape
    free = ~words
    bitpos = lax.broadcasted_iota(jnp.uint32, (block_n, nwords, 32), 2)
    is_free = ((free[:, :, None] >> bitpos) & jnp.uint32(1)) == jnp.uint32(1)
    pos = (
        lax.broadcasted_iota(jnp.int32, (block_n, nwords, 32), 1) * 32
        + bitpos.astype(jnp.int32)
    )
    pos = jnp.where(is_free & (pos <= bound), pos, jnp.int32(bound + 2))
    return jnp.min(jnp.min(pos, axis=2), axis=1, keepdims=True) + 1
