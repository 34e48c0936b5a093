"""Backend dispatch for the engine fast paths (DESIGN.md §15).

One tiny resolver decides, for every engine entry point, whether the ragged
super-step runs through the fused Pallas kernel (``kernels/superstep``) or
the pure-JAX formulation.  Both produce bit-identical colors — the kernel
implements the exact same conflict rule and bitset FirstFit arithmetic — so
the choice is purely a performance policy and the resolver is the single
place that policy lives:

* ``backend=None``   — legacy: honor the per-call ``use_kernel`` knob
  (``use_kernel=True`` has always meant "route through the Pallas kernels").
* ``backend="jax"``  — force the pure-JAX engine.  Contradicting it with
  ``use_kernel=True`` raises instead of silently picking a side.
* ``backend="pallas"`` — force the gathered-tile kernel path.  On non-TPU
  backends the kernels run in ``interpret=True`` mode (see
  ``kernels/superstep/ops.py``), slow but bit-identical — which is what
  the differential test matrix runs in CI.  On a TPU they compile
  (``tests/test_tpu_compile.py``).
* ``backend="pallas-csr"`` — force the CSR-resident fused kernel path
  (DESIGN.md §18): the kernel gathers straight from the DeviceCSR arrays,
  no materialized ``(w, W)`` tile in HBM.  Engines or configurations that
  can't feed it CSR arrays (dense batch layouts, multi-chunk classes,
  packed-word overflow) fall back to the gathered kernel — bit-identical,
  so the fallback is invisible except in wall-clock.  Its kernels do not
  compile for the TPU (they load from ``ANY``-space refs directly), so on
  a TPU this backend is refused; it runs in interpret mode elsewhere.
* ``backend="auto"`` — ``jax`` on every platform: no kernel has yet been
  measured faster than the pure-JAX engine on the chip.  The legacy
  ``use_kernel=True`` knob keeps meaning the gathered kernel.

Engines that cannot host any kernel (the §13 multi-device sharded engine —
``shard_map`` bodies stay pure-JAX) treat both pallas backends as an
automatic fallback to pure-JAX: bit-identity makes the fallback invisible
except in wall-clock.
"""
from __future__ import annotations

import warnings

import jax

__all__ = ["resolve_backend", "kernel_mode", "BACKENDS"]

BACKENDS = ("jax", "pallas", "pallas-csr", "auto")


def resolve_backend(backend: str | None, use_kernel: bool = False) -> str:
    """Resolve ``backend=`` to ``"jax"``, ``"pallas"`` or ``"pallas-csr"``.

    ``use_kernel`` is the legacy per-call knob, DEPRECATED since §19: a
    True value warns and keeps meaning the gathered-kernel path for one
    more release (the compat shim), decides only when ``backend`` is None
    or "auto", and conflicts loudly with ``backend="jax"``.  The unified
    entry points translate it into ``backend=`` before reaching here
    (``repro.options.ColorOptions.normalize``); this shim covers direct
    engine calls.
    """
    if use_kernel:
        from repro.options import _DEPRECATION_MSG

        warnings.warn(_DEPRECATION_MSG, DeprecationWarning, stacklevel=3)
    if backend in (None, "auto"):
        return "pallas" if use_kernel else "jax"
    if backend == "jax":
        if use_kernel:
            raise ValueError(
                "backend='jax' contradicts use_kernel=True; drop one of them "
                "(backend='pallas' is the kernel path)")
        return "jax"
    if backend == "pallas-csr" and jax.default_backend() == "tpu":
        raise ValueError(
            "backend='pallas-csr' does not compile for the TPU: its kernels "
            "load from ANY-space refs, which Mosaic only reaches by DMA; use "
            "backend='pallas' or 'jax'")
    if backend in ("pallas", "pallas-csr"):
        return backend
    raise ValueError(
        f"unknown backend {backend!r}; options: {', '.join(BACKENDS)}")


def kernel_mode(resolved: str):
    """Map a resolved backend to the engine-internal ``use_kernel`` value.

    ``False`` — pure JAX; ``True`` — gathered-tile Pallas kernel;
    ``"csr"`` — CSR-resident fused kernel (gathered fallback where the CSR
    arrays aren't available).  All three are hashable, so the value can sit
    in jit static args; ``"csr"`` is truthy, so boolean-ish "any kernel?"
    checks keep working.
    """
    return {"jax": False, "pallas": True, "pallas-csr": "csr"}[resolved]
