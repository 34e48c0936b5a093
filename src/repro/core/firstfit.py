"""Vectorized FirstFit variants (paper Alg. 4) over padded neighbor colors.

All variants take ``neigh_colors`` of shape ``(w, W)`` int32 — the gathered
colors of up to ``W`` neighbors per worklist vertex, 0 meaning
"no neighbor / uncolored" — and return the smallest permissible color in
``[1, W+1]`` per row.  Greedy guarantees a free color exists in that range
(W neighbors can forbid at most W of the W+1 candidates).

The answer is also at most the row's largest color + 1, so a bitset of
``bitset_words(C)`` words gives the same answer as the full ``(W+1)/32``
whenever no row holds a color above C: the word count follows the colors
in play, not the row width (a hub row of 162,605 lanes in a graph of 282
colors needs 16 words, not 5,082).

Variants (see DESIGN.md §3 for the CUDA→TPU mapping):

* ``scan``   — the paper's baseline colorMask: scatter forbidden counts into a
               per-vertex (W+2)-wide mask, then scan for the first zero.  This
               is the memory-traffic-heavy variant the bitset replaces.
* ``sort``   — sort neighbor colors and walk the first gap (an alternative
               low-memory baseline; O(W log W) compute, O(w·W) memory).
* ``bitset`` — the paper's §3.2 contribution: forbidden colors packed into
               uint32 words; first permissible color via find-first-set.  TPU
               has no ``__ffs`` intrinsic, so ffs = popcount(lsb−1) with the
               two's-complement lsb trick — both single VPU ops.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.csr import next_pow2

__all__ = ["firstfit_scan", "firstfit_sort", "firstfit_bitset", "FF_FUNCS",
           "FF_MIN_WORDS", "bitset_words", "ffs_u32"]

# fewest bitset words a bounded FirstFit asks for: the word count is a jit
# key, and with 16 words one program serves every graph of up to 510 colors
FF_MIN_WORDS = 16


def firstfit_scan(neigh_colors: jax.Array) -> jax.Array:
    """colorMask analogue: per-row forbidden counts + first-zero scan."""
    w, W = neigh_colors.shape
    C = W + 1  # candidate colors 1..C
    cols = jnp.where((neigh_colors >= 1) & (neigh_colors <= C), neigh_colors, 0)
    mask = jnp.zeros((w, C + 1), dtype=jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32)[:, None], (w, W))
    mask = mask.at[rows, cols].add(1)  # column 0 is a trash slot
    permissible = mask[:, 1:] == 0  # (w, C)
    return jnp.argmax(permissible, axis=1).astype(jnp.int32) + 1


def firstfit_sort(neigh_colors: jax.Array) -> jax.Array:
    """Sort + first-gap walk: f advances past each sorted color it meets."""
    s = jnp.sort(neigh_colors, axis=1)
    w, W = s.shape

    def body(d, f):
        return jnp.where(s[:, d] == f, f + 1, f)

    f = lax.fori_loop(0, W, body, jnp.ones((w,), dtype=jnp.int32))
    return f


def _forbidden_words(neigh_colors: jax.Array, nwords: int) -> jax.Array:
    """Pack forbidden colors 1..32*nwords into uint32 bit words (bit c-1)."""
    idx = neigh_colors.astype(jnp.int32) - 1  # -1 for "no color"
    valid = idx >= 0
    word_of = jnp.where(valid, idx >> 5, -1)
    bit = (jnp.where(valid, idx, 0) & 31).astype(jnp.uint32)
    bits = jnp.where(valid, jnp.uint32(1) << bit, jnp.uint32(0))
    words = []
    for wd in range(nwords):
        contrib = jnp.where(word_of == wd, bits, jnp.uint32(0))
        words.append(
            lax.reduce(contrib, jnp.uint32(0), lax.bitwise_or, dimensions=(1,))
        )
    return jnp.stack(words, axis=1)  # (w, nwords)


def ffs_u32(x: jax.Array) -> jax.Array:
    """Find-first-set per uint32 element: index of lowest 1 bit, 32 if x==0.

    TPU adaptation of CUDA ``__ffs``: lsb = x & (~x + 1); index = popcount(lsb-1).
    """
    lsb = x & (~x + jnp.uint32(1))
    tz = lax.population_count(lsb - jnp.uint32(1))
    return jnp.where(x == 0, jnp.uint32(32), tz).astype(jnp.int32)


def bitset_words(max_color: int, extra: int = 1) -> int:
    """Static bitset words for tiles whose colors are at most ``max_color``
    and whose rows may each see up to ``extra - 1`` colors more (one per
    earlier chunk of a chunked step): their answers are at most
    ``max_color + extra``, which ``32 * words`` bits always hold.  A power
    of two, at least ``FF_MIN_WORDS``, so few programs serve every count;
    ``firstfit_bitset`` caps it at the tile's own ``(W + 1) / 32``."""
    return max(FF_MIN_WORDS, next_pow2(-(-(max_color + extra + 1) // 32)))


def firstfit_bitset(neigh_colors: jax.Array,
                    nwords: int | None = None) -> jax.Array:
    """The paper's bitset FirstFit: bit words + find-first-set.

    ``nwords`` (static) caps the bitset below the full ``(W + 1) / 32``
    words.  The answer is the same whenever every row's answer fits in
    ``32 * nwords`` bits, which holds when no row holds a color of
    ``32 * nwords`` or more (an answer is at most its row's largest
    color + 1): colors past the last word are left out, and the smallest
    free color lies before them.  ``bitset_words`` gives such a bound.
    """
    w, W = neigh_colors.shape
    nbits = W + 1
    full = (nbits + 31) // 32
    nwords = full if nwords is None else min(int(nwords), full)
    words = _forbidden_words(neigh_colors, nwords)
    # forbid phantom candidates beyond W+1 so ffs never exceeds the greedy bound
    tail = nwords * 32 - nbits
    if tail > 0:
        pad_mask = jnp.uint32(((1 << tail) - 1) << (32 - tail))
        words = words.at[:, nwords - 1].set(words[:, nwords - 1] | pad_mask)
    free = ~words
    tz = ffs_u32(free)  # (w, nwords), 32 where word full
    has = free != 0
    first_w = jnp.argmax(has, axis=1).astype(jnp.int32)
    tz_sel = jnp.take_along_axis(tz, first_w[:, None], axis=1)[:, 0]
    return first_w * 32 + tz_sel + 1


FF_FUNCS = {
    "scan": firstfit_scan,
    "sort": firstfit_sort,
    "bitset": firstfit_bitset,
}
