"""Ahead-of-time compiles for a described TPU v5e chip (no chip attached).

The TPU compiler is installed next to JAX, so a kernel can be compiled for a
chip that is only described.  This catches what interpret mode cannot: a
primitive Mosaic does not lower, a block shape the chip refuses, a kernel
that does not fit VMEM.  Nothing here runs; it proves compilation only.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

WORKLIST = 65536
WIDTHS = [16, 64, 376]          # narrow class, mid class, rmat-g's max degree

# rmat-g at its paper size (n = 1,048,576; 10.47M entries; max degree 376):
# the auto-tiled classes' sizes and widths of its first full super-step
RMAT_G_N = 1_048_576
RMAT_G_ENTRIES = 10_473_742
RMAT_G_CLASSES = ((45241, 376), (131026, 32), (242471, 16), (629838, 8))
# the round tail's classes of rmat-g's stalled tail: row lengths rounded up
# to powers of two, at least 16
RMAT_G_TAIL_CLASSES = ((10, 376), (291, 256), (5801, 128), (39139, 64),
                       (131026, 32), (872309, 16))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to, but not read back
    # from, the persistent cache: keep it out of the way
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _hlo(jitted, *args, **static):
    return jitted.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("W", WIDTHS)
def test_superstep_kernel_compiles_for_v5e(one_chip, W):
    from repro.kernels.superstep.ops import _pick_block_n, _run

    w = WORKLIST
    hlo = _hlo(_run, _spec(one_chip, (w, 3)), *[_spec(one_chip, (w, W))] * 3,
               heuristic="degree", block_n=_pick_block_n(w, W),
               interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("W", WIDTHS)
def test_conflict_kernel_compiles_for_v5e(one_chip, W):
    from repro.kernels.conflict.ops import _pick_block_n, _run

    w = WORKLIST
    hlo = _hlo(_run, _spec(one_chip, (w, 3)), *[_spec(one_chip, (w, W))] * 3,
               heuristic="degree", block_n=_pick_block_n(w, W),
               interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("W", WIDTHS)
def test_firstfit_kernel_compiles_for_v5e(one_chip, W):
    from repro.kernels.firstfit.ops import _pick_block_n, _run

    w = WORKLIST
    hlo = _hlo(_run, _spec(one_chip, (w, W)),
               block_n=_pick_block_n(w, W), interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("W", WIDTHS)
def test_d2_kernel_compiles_for_v5e(one_chip, W):
    from repro.kernels.d2.ops import _pick_block_n, _run

    w = WORKLIST
    hlo = _hlo(_run, _spec(one_chip, (w, W)), _spec(one_chip, (w, W)),
               block_n=_pick_block_n(w, W, W), interpret=False)
    assert "tpu_custom_call" in hlo


def test_ragged_superstep_compiles_for_v5e_at_rmat_g_size(one_chip):
    """The default (pure-JAX) engine's jitted super-step, as the ragged
    engine dispatches it on rmat-g: every degree class in one program."""
    from repro.core.coloring import provider_tiled_superstep
    from repro.core.csr import DeviceCSR

    n, dmax = RMAT_G_N, RMAT_G_CLASSES[0][1]
    provider = DeviceCSR(_spec(one_chip, (n + 1,)),
                         _spec(one_chip, (RMAT_G_ENTRIES + dmax,)),
                         _spec(one_chip, (n + 1,)), n, dmax)
    wls = tuple(_spec(one_chip, (cap,)) for cap, _ in RMAT_G_CLASSES)
    K = len(RMAT_G_CLASSES)
    hlo = _hlo(provider_tiled_superstep, provider,
               _spec(one_chip, (n + 1,)), _spec(one_chip, (n + 1,)), wls,
               widths=tuple(W for _, W in RMAT_G_CLASSES),
               heuristic="degree", kind="bitset", use_kernel=False,
               chunks=(1,) * K, pack_degrees=True)
    assert "tpu_custom_call" not in hlo   # pure JAX: no kernel expected


@pytest.mark.parametrize("program", ["setup", "frontier", "round_wide",
                                     "round_narrow"])
def test_round_tail_compiles_for_v5e_at_rmat_g_size(one_chip, program):
    """The round tail's programs as rmat-g's stalled tail runs them: the
    counters along C, the per-class frontier counts, and the rounds of
    its widest (376 x 256) and largest (16 x 65,536) tiles."""
    from repro.core import coloring as C
    from repro.core.csr import DeviceCSR, next_pow2

    n, dmax = RMAT_G_N, RMAT_G_CLASSES[0][1]
    provider = DeviceCSR(_spec(one_chip, (n + 1,)),
                         _spec(one_chip, (RMAT_G_ENTRIES + dmax,)),
                         _spec(one_chip, (n + 1,)), n, dmax)
    vec = _spec(one_chip, (n + 1,))
    K = len(RMAT_G_TAIL_CLASSES)
    if program == "setup":
        members = tuple(_spec(one_chip, (max(256, next_pow2(c)),))
                        for c, _ in RMAT_G_TAIL_CLASSES)
        hlo = _hlo(C.provider_tail_setup, provider, vec,
                   _spec(one_chip, (n,)), members,
                   widths=tuple(W for _, W in RMAT_G_TAIL_CLASSES),
                   by_entries=True)
    elif program == "frontier":
        hlo = _hlo(C.provider_tail_frontier, vec, vec, vec, classes=K)
    else:
        cap, width = (256, 376) if program == "round_wide" else (65536, 16)
        hlo = _hlo(C.provider_tail_round, provider, vec, vec,
                   _spec(one_chip, (K, n)), _spec(one_chip, ()), cap=cap,
                   width=width, kind="bitset")
    assert "tpu_custom_call" not in hlo


def test_auto_backend_is_jax_on_tpu_and_csr_kernel_refused(monkeypatch):
    """On a TPU, ``auto`` resolves to the pure-JAX path and ``pallas-csr``
    (whose kernels Mosaic refuses) is rejected with the reason."""
    from repro.kernels.dispatch import resolve_backend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend("auto") == "jax"
    assert resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="does not compile for the TPU"):
        resolve_backend("pallas-csr")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_backend("auto") == "jax"
    assert resolve_backend("pallas-csr") == "pallas-csr"   # interpret mode
