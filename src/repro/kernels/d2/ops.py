"""jit'd wrapper for the distance-2 bitset FirstFit Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.d2.kernel import d2_firstfit_pallas_call

__all__ = ["d2_firstfit_bitset_tpu"]

_VMEM_BUDGET = 2 * 1024 * 1024  # bytes for the two neighbor-color tiles


def _pick_block_n(w: int, W1: int, W2: int) -> int:
    by_vmem = max(8, _VMEM_BUDGET // max((W1 + W2) * 4, 1))
    # round down to a multiple of 8 (sublane), cap at the row count
    return max(8, (min(by_vmem, 256, w) // 8) * 8)


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def _run(nc1, nc2, *, block_n: int, interpret: bool):
    return d2_firstfit_pallas_call(
        nc1.shape[0], nc1.shape[1], nc2.shape[1], block_n, interpret
    )(nc1, nc2)[:, 0]


def d2_firstfit_bitset_tpu(
    nc1: jax.Array,
    nc2: jax.Array,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """FirstFit over hop-1 ``(w, W1)`` + hop-2 ``(w, W2)`` color tiles.

    Returns colors ``(w,)`` in ``[1, W1+W2+1]``.  ``interpret`` defaults to
    True off-TPU (CPU validation mode) and False on real TPU backends.
    """
    w = nc1.shape[0]
    if w == 0:
        return jnp.zeros((0,), jnp.int32)
    interpret = (jax.default_backend() != "tpu") if interpret is None else interpret
    block_n = block_n or _pick_block_n(w, nc1.shape[1], nc2.shape[1])
    return _run(
        nc1.astype(jnp.int32), nc2.astype(jnp.int32),
        block_n=block_n, interpret=interpret,
    )
