"""Hot-path dispatch between the Pallas kernels and their jnp twins.

`fused_local_phase` is the one kernel on a topology path (the pointer init
of `_manifold_block` / `_cc_block` and of the pure grid entry points).  The
dispatch is static — decided from the backend and the input's shape, never
from a caught failure:

* on a TPU, the compiled kernel whenever it applies: a 3-D field, a
  connectivity in `KERNEL_CONNECTIVITIES`, and int32 ids (Pallas TPU has no
  int64, so grids of 2**31 vertices or more take the jnp init);
* everywhere else the jnp init, which XLA fuses well on the CPU.  The
  kernel's interpret mode is for tests only (``impl="kernel"``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .fused_local_phase import (KERNEL_CONNECTIVITIES,
                                fused_local_phase as _fused_kernel)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ghost_keep(shape, ghost_axes):
    """Boolean array marking the first and last layer along each ghost
    axis, built from iota comparisons (no host array reaches the trace)."""
    keep = jnp.zeros(shape, bool)
    for a in ghost_axes:
        g = lax.broadcasted_iota(jnp.int32, shape, a)
        keep = keep | (g == 0) | (g == shape[a] - 1)
    return keep


def fused_local_phase(field, connectivity: int = 6, mode: str = "manifold",
                      ghost_axes: tuple = (), impl: str = "auto",
                      id_dtype=None):
    """Block-local pointer init: steepest argmax (``mode="manifold"``) or
    largest masked neighbor id (``mode="cc"``, -1 where unmasked), with the
    first/last layer along each of `ghost_axes` forced to self-pointers
    (the distributed ghost layer, Alg. 1 lines 6-8; in cc mode only where
    masked).  Returns the (X, Y, Z) pointer array; every implementation
    returns the same bits.

    impl="auto": the module docstring's rule;
    impl="kernel": force the kernel (interpret mode off-TPU — tests);
    impl="ref": force the jnp init.
    2-D fields and connectivities without a 3-D table always take the jnp
    init.
    """
    if impl not in ("auto", "kernel", "ref"):
        raise ValueError(f"impl must be auto|kernel|ref, got {impl!r}")
    if mode not in ("manifold", "cc"):
        raise ValueError(f"mode must be 'manifold' or 'cc', got {mode!r}")
    applies = field.ndim == 3 and connectivity in KERNEL_CONNECTIVITIES
    if impl == "kernel" and applies:
        return _fused_kernel(field, connectivity, mode, tuple(ghost_axes),
                             interpret=not _on_tpu(), id_dtype=id_dtype)
    if (impl == "auto" and applies and _on_tpu() and field.size < 2**31
            and id_dtype in (None, jnp.int32)):
        return _fused_kernel(field, connectivity, mode, tuple(ghost_axes),
                             interpret=False)
    from repro.core.steepest import grid_steepest, grid_mask_argmax
    if mode == "manifold":
        d0 = grid_steepest(field, connectivity)
    else:
        d0 = grid_mask_argmax(field, connectivity)
    if id_dtype is not None:
        d0 = d0.astype(id_dtype)
    d0 = d0.reshape(field.shape)
    if ghost_axes:
        keep = ghost_keep(field.shape, ghost_axes)
        if mode == "cc":
            keep = keep & (field != 0)
        ids = lax.broadcasted_iota(d0.dtype, field.shape, 0)
        for a in range(1, field.ndim):
            ids = ids * field.shape[a] + lax.broadcasted_iota(
                d0.dtype, field.shape, a)
        d0 = jnp.where(keep, ids, d0)
    return d0
