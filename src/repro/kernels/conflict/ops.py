"""jit'd wrapper for the conflict-detect Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.conflict.kernel import conflict_pallas_call

__all__ = ["conflict_tpu"]

_VMEM_BUDGET = 2 * 1024 * 1024


def _pick_block_n(w: int, W: int) -> int:
    by_vmem = max(8, _VMEM_BUDGET // max(W * 4 * 3, 1))
    return max(8, (min(by_vmem, 256, w) // 8) * 8)


@partial(jax.jit, static_argnames=("heuristic", "block_n", "interpret"))
def _run(me, nid, nc, nd, *, heuristic, block_n, interpret):
    return conflict_pallas_call(
        me.shape[0], nid.shape[1], block_n, heuristic, interpret
    )(me, nid, nc, nd)[:, 0]


def conflict_tpu(
    ids: jax.Array,
    neigh_ids: jax.Array,
    my_colors: jax.Array,
    neigh_colors: jax.Array,
    my_deg: jax.Array,
    neigh_deg: jax.Array,
    heuristic: str = "degree",
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Loser flags (bool, (w,)) for speculative conflicts; kernel-backed."""
    w, W = neigh_ids.shape
    if w == 0:
        return jnp.zeros((0,), bool)
    interpret = (jax.default_backend() != "tpu") if interpret is None else interpret
    block_n = block_n or _pick_block_n(w, W)
    me = jnp.stack(
        [ids.astype(jnp.int32), my_colors.astype(jnp.int32), my_deg.astype(jnp.int32)],
        axis=1,
    )
    lose = _run(
        me,
        neigh_ids.astype(jnp.int32),
        neigh_colors.astype(jnp.int32),
        neigh_deg.astype(jnp.int32),
        heuristic=heuristic,
        block_n=block_n,
        interpret=interpret,
    )
    return lose.astype(bool)
