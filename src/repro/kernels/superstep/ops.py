"""jit'd wrapper for the fused super-step Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.superstep.kernel import superstep_pallas_call

__all__ = ["superstep_tpu"]

# VMEM budget for one grid step's working set; see _pick_block_n
_VMEM_BUDGET = 2 * 1024 * 1024


def _pick_block_n(w: int, W: int, *, tiles: int = 3) -> int:
    """Largest block_n (multiple of 8, capped at 256) fitting _VMEM_BUDGET.

    The per-row working set is ``tiles`` int32 ``(block_n, W)`` tiles
    (gathered kernel: neighbor ids/colors/degrees; CSR kernel adds the
    packed-gather tile, hence ``tiles=4``) PLUS the FirstFit state the
    kernel allocates per row: ``nwords`` uint32 bitset words and the
    ``(nwords, 32)`` int32 position expansion the min-reduce scans.
    """
    nwords = (W + 1 + 31) // 32
    per_row = tiles * W * 4 + nwords * 4 + nwords * 32 * 4
    by_vmem = max(8, _VMEM_BUDGET // max(per_row, 1))
    return max(8, (min(by_vmem, 256, w) // 8) * 8)


@partial(jax.jit, static_argnames=("heuristic", "block_n", "interpret"))
def _run(me, nid, nc, nd, *, heuristic, block_n, interpret):
    newc, need = superstep_pallas_call(
        me.shape[0], nid.shape[1], block_n, heuristic, interpret
    )(me, nid, nc, nd)
    return newc[:, 0], need[:, 0]


def superstep_tpu(
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
) -> tuple[jax.Array, jax.Array]:
    """Fused conflict-check + FirstFit over one ``(w, W)`` neighbor tile.

    Returns ``(new_colors, need)``: the post-step color per worklist row and
    a bool flag marking rows that were recolored (and so need re-verification
    next super-step).  Sentinel masking is the caller's job — the kernel has
    no notion of the vertex count.
    """
    w, W = neigh_ids.shape
    if w == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool)
    interpret = (jax.default_backend() != "tpu") if interpret is None else interpret
    block_n = block_n or _pick_block_n(w, W)
    me = jnp.stack(
        [ids.astype(jnp.int32), my_colors.astype(jnp.int32),
         my_deg.astype(jnp.int32)],
        axis=1,
    )
    newc, need = _run(
        me,
        neigh_ids.astype(jnp.int32),
        neigh_colors.astype(jnp.int32),
        neigh_deg.astype(jnp.int32),
        heuristic=heuristic,
        block_n=block_n,
        interpret=interpret,
    )
    return newc, need.astype(bool)
