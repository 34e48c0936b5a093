"""jit'd wrapper for the bitset FirstFit Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.firstfit.kernel import firstfit_pallas_call

__all__ = ["firstfit_bitset_tpu"]

_VMEM_BUDGET = 2 * 1024 * 1024  # bytes for the neighbor-color tile


def _pick_block_n(w: int, W: int) -> int:
    by_vmem = max(8, _VMEM_BUDGET // max(W * 4, 1))
    # round down to a multiple of 8 (sublane), cap at the row count
    bn = max(8, (min(by_vmem, 256, w) // 8) * 8)
    return bn


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def _run(nc, *, block_n: int, interpret: bool):
    return firstfit_pallas_call(
        nc.shape[0], nc.shape[1], block_n, interpret)(nc)[:, 0]


def firstfit_bitset_tpu(
    neigh_colors: jax.Array,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """FirstFit over padded neighbor colors ``(w, W)`` -> colors ``(w,)``.

    ``interpret`` defaults to True off-TPU (CPU validation mode per the task
    contract) and False on real TPU backends.
    """
    w, W = neigh_colors.shape
    if w == 0:
        return jnp.zeros((0,), jnp.int32)
    interpret = (not _on_tpu()) if interpret is None else interpret
    block_n = block_n or _pick_block_n(w, W)
    return _run(neigh_colors.astype(jnp.int32), block_n=block_n, interpret=interpret)
