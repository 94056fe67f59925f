"""Pallas TPU kernel: steepest-neighbor stencil (DPC init, Alg. 1 l. 3-5).

The DPC hot spot on init is a 6/14-point argmax stencil over the order field.
TPU adaptation: tile the grid into x-slabs that fit VMEM; each tile is loaded
once together with two pre-sliced halo planes (avoids overlapping BlockSpecs),
and the argmax over the static offset list is fully vectorised on the VPU —
one HBM read + one HBM write per voxel instead of the scalar neighbor loop of
the CPU implementation.

Layout per grid step i (grid = X / block_x):
  center ref: (block_x, Y, Z)   <- order[i*block_x : (i+1)*block_x]
  lo ref:     (1, Y, Z)         <- plane i*block_x - 1   (padded outside)
  hi ref:     (1, Y, Z)         <- plane (i+1)*block_x   (padded outside)
  out ref:    (block_x, Y, Z)   -> global flat id of the steepest neighbor
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.steepest import neighbor_offsets


def _kernel(center, lo, hi, out, *, offsets, block_x, R, fill):
    i = pl.program_id(0)
    ext = jnp.concatenate([lo[...], center[...], hi[...]], axis=0)
    z = ext.shape[2]
    # global flat ids of the extended tile (row-major, x-major layout)
    base = (i * block_x - 1) * R
    gids = base + jax.lax.broadcasted_iota(jnp.int32, ext.shape, 0) * R \
        + jax.lax.broadcasted_iota(jnp.int32, ext.shape, 1) * z \
        + jax.lax.broadcasted_iota(jnp.int32, ext.shape, 2)

    def shifted(a, off, fill_val):
        """a[p + off] within the ext tile, fill outside (static shifts)."""
        pads = [(max(-o, 0), max(o, 0)) for o in off]
        padded = jnp.pad(a, pads, constant_values=fill_val)
        sl = tuple(slice(max(o, 0), max(o, 0) + s)
                   for o, s in zip(off, a.shape))
        return padded[sl]

    # stacked candidates + ONE argmax (not chained per-offset selects, which
    # send XLA:CPU fusion into minutes-long compiles at connectivity >= 14);
    # self is candidate 0, so first-max-wins keeps self on ties — ties only
    # occur at the inert fill value
    cand_val = jnp.stack([ext] + [shifted(ext, off, fill)
                                  for off in offsets])
    cand_idx = jnp.stack([gids] + [shifted(gids, off, -1)
                                   for off in offsets])
    choice = jnp.argmax(cand_val, axis=0)
    out[...] = jnp.take_along_axis(cand_idx, choice[None], axis=0)[0][1:-1]


@functools.partial(jax.jit,
                   static_argnames=("connectivity", "block_x", "interpret"))
def steepest_neighbor(order: jax.Array, connectivity: int = 6,
                      block_x: int = 8, *, interpret: bool) -> jax.Array:
    """order: (X, Y, Z) int32 (unique values >= 0).  Returns (X, Y, Z) int32
    global flat ids.  On-domain boundary handled by -fill halo planes."""
    if order.ndim != 3:
        raise ValueError(
            f"steepest_neighbor is a 3-D x-slab kernel; got a {order.ndim}-D "
            f"field of shape {order.shape} — repro.kernels.ops dispatches "
            "such inputs to the jnp grid_steepest fallback")
    try:
        offsets = neighbor_offsets(3, connectivity)
    except ValueError as e:
        raise ValueError(
            f"steepest_neighbor: connectivity {connectivity} has no 3-D "
            "offset table; repro.kernels.ops dispatches it to the jnp "
            "fallback") from e
    x, y, z = order.shape
    if x % block_x:
        block_x = 1
    fill = jnp.iinfo(order.dtype).min
    nblk = x // block_x
    # pre-sliced halo planes: lo[i] = order[i*bx - 1], hi[i] = order[(i+1)*bx]
    padded = jnp.concatenate([
        jnp.full((1, y, z), fill, order.dtype), order,
        jnp.full((1, y, z), fill, order.dtype)], axis=0)
    lo = padded[0::block_x][:nblk]
    hi = padded[block_x + 1::block_x][:nblk]

    grid = (nblk,)
    kernel = functools.partial(_kernel, offsets=offsets, block_x=block_x,
                               R=y * z, fill=fill)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_x, y, z), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, y, z), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, y, z), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_x, y, z), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((x, y, z), jnp.int32),
        interpret=interpret,
    )(order, lo, hi)
