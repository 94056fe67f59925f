"""Pallas TPU kernel: fused block-local pointer init (DPC Alg. 1 l. 3-8).

The block-local phase of Alg. 1/3 starts with a stencil pass over the
extended block: every vertex points at its steepest neighbor
(``mode="manifold"``: the argmax of the order field over itself and the
stencil) or at the largest masked neighbor id (``mode="cc"``: -1 where
unmasked), and the ghost layers of a distributed block pretend to be
maxima / roots (Alg. 1 lines 6-8).  This kernel does all of it in one HBM
read and one HBM write per vertex; the pointer doubling that follows is the
global `path_compress` loop.

Tiling (Mosaic): the grid is (x tiles, y tiles) over a ``(bx, by, Z)``
block with the whole z extent in the lane axis.

* ``bx`` is a leading (untiled) dimension: any size; a ragged last tile is
  fine because every neighbor read is masked by its global coordinate.
* ``by`` is either the whole y extent or a multiple of 8 that divides it
  (the sublane tiling).  Neighbors one row past the tile come from two
  extra ``(bx, 8, Z)`` reads of the same array (rows 7 / 0 of the adjacent
  8-row groups), so no input is ever re-laid-out in HBM.
* x neighbors come from two ``(1, by, Z)`` plane reads; stencils with an
  x/y diagonal (connectivity 14/18/26) also read the four ``(1, 8, Z)``
  corner groups.
* Shifts inside the tile are `pltpu.roll` rotations; the argmax is a
  compare-and-select chain in int32 (self first, then the stencil in table
  order, strict ``>`` — the same first-max-wins rule as `grid_steepest`).
* The ghost override is built from iota comparisons, never from an input
  array.

There is no in-tile pointer doubling: it needs a 1-D dynamic gather, which
Mosaic does not lower.  On the CPU the kernel runs in interpret mode; the
caller chooses (`interpret=` has no default).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.steepest import neighbor_offsets

# connectivities with a 3-D offset table (the kernel is 3-D only; ops.py
# sends every other case to the jnp init)
KERNEL_CONNECTIVITIES = (6, 14, 18, 26)

# target elements per (bx, by, Z) tile: 512 KiB of int32, which keeps the
# double-buffered inputs plus the kernel's handful of live tiles well inside
# the default scoped VMEM
_TILE_ELEMS = 1 << 17


def choose_tile(shape):
    """(bx, by) for a field of `shape` (see the module docstring)."""
    x, y, z = shape
    if y % 8:
        by = y
    else:
        fits = [d for d in range(8, y + 1, 8)
                if y % d == 0 and d * z * 8 <= _TILE_ELEMS]
        by = max(fits) if fits else 8
    bx = max(1, min(x, _TILE_ELEMS // (by * z)))
    return bx, by


def _xshift(center, lo, hi, dx):
    """Planes x+dx of the tile: `center` (bx, ...) with the plane before
    (`lo`) / after (`hi`) it, each (1, ...)."""
    if dx == 0:
        return center
    if center.shape[0] == 1:
        return hi if dx > 0 else lo
    if dx > 0:
        return jnp.concatenate([center[1:], hi], axis=0)
    return jnp.concatenate([lo, center[:-1]], axis=0)


def _kernel(*refs, offsets, shape, bx, by, mode, fill, ghost_axes,
            y_halo, corners, id_dtype):
    c_ref, xlo_ref, xhi_ref = refs[:3]
    rest = refs[3:]
    if y_halo:
        ylo_ref, yhi_ref = rest[:2]
        rest = rest[2:]
        if corners:
            ll_ref, lh_ref, hl_ref, hh_ref = rest[:4]
            rest = rest[4:]
    (out_ref,) = rest
    X, Y, Z = shape
    R = Y * Z
    i, j = pl.program_id(0), pl.program_id(1)

    c = c_ref[...]
    tshape = c.shape
    gx = i * bx + jax.lax.broadcasted_iota(jnp.int32, tshape, 0)
    ly = jax.lax.broadcasted_iota(jnp.int32, tshape, 1)
    gy = j * by + ly
    gz = jax.lax.broadcasted_iota(jnp.int32, tshape, 2)
    gid = (gx.astype(id_dtype) * R + gy.astype(id_dtype) * Z
           + gz.astype(id_dtype))

    planes = {dx: _xshift(c, xlo_ref[...], xhi_ref[...], dx)
              for dx in (-1, 0, 1)}
    if y_halo:
        # row y0-1 / y0+by of each x-shifted plane: row 7 of the 8-row
        # group above, row 0 of the group below
        ylo, yhi = ylo_ref[:, 7:8, :], yhi_ref[:, 0:1, :]
        if corners:
            rows = {-1: {dx: _xshift(ylo, ll_ref[:, 7:8, :],
                                     hl_ref[:, 7:8, :], dx)
                         for dx in (-1, 0, 1)},
                    1: {dx: _xshift(yhi, lh_ref[:, 0:1, :],
                                    hh_ref[:, 0:1, :], dx)
                        for dx in (-1, 0, 1)}}
        else:
            rows = {-1: {0: ylo}, 1: {0: yhi}}

    def neighbor(dx, dy, dz):
        """(value at p + (dx, dy, dz), in-domain flag); out-of-tile rows
        come from the halo reads, out-of-domain cells are flagged."""
        v = planes[dx]
        valid = None
        if dy:
            v = pltpu.roll(v, (-dy) % by, 1)
            if y_halo:
                edge = by - 1 if dy > 0 else 0
                v = jnp.where(ly == edge,
                              jnp.broadcast_to(rows[dy][dx], tshape), v)
        if dz:
            v = pltpu.roll(v, (-dz) % Z, 2)
        for g, d, n in ((gx, dx, X), (gy, dy, Y), (gz, dz, Z)):
            if d:
                ok = (g + d >= 0) & (g + d < n)
                valid = ok if valid is None else valid & ok
        return v, valid

    minus1 = jnp.asarray(-1, id_dtype)
    if mode == "manifold":
        best_v, ptr = c, gid
        for off in offsets:
            v, valid = neighbor(*off)
            v = jnp.where(valid, v, fill)
            better = v > best_v
            best_v = jnp.where(better, v, best_v)
            delta = off[0] * R + off[1] * Z + off[2]
            ptr = jnp.where(better, gid + delta, ptr)
        masked = None
    else:  # "cc": largest masked neighbor id (incl. self), -1 unmasked
        masked = c != 0
        best = jnp.where(masked, gid, minus1)
        for off in offsets:
            v, valid = neighbor(*off)
            delta = off[0] * R + off[1] * Z + off[2]
            cand = jnp.where(valid & (v != 0), gid + delta, minus1)
            best = jnp.maximum(best, cand)
        ptr = jnp.where(masked, best, minus1)

    if ghost_axes:
        # ghost layers (first/last index along each decomposed axis)
        # pretend to be maxima / roots — Alg. 1 lines 6-8
        keep = None
        for a in ghost_axes:
            g = (gx, gy, gz)[a]
            on = (g == 0) | (g == shape[a] - 1)
            keep = on if keep is None else keep | on
        if masked is not None:
            keep = keep & masked
        ptr = jnp.where(keep, gid, ptr)
    out_ref[...] = ptr


@functools.partial(jax.jit, static_argnames=(
    "connectivity", "mode", "ghost_axes", "interpret", "tile", "id_dtype"))
def fused_local_phase(field: jax.Array, connectivity: int = 6,
                      mode: str = "manifold", ghost_axes: tuple = (), *,
                      interpret: bool, tile=None, id_dtype=None):
    """Steepest / mask-argmax pointer init with the ghost override.

    field: (X, Y, Z) int order field (``mode="manifold"``: unique values,
    any inert fill strictly above ``iinfo.min``) or bool/int feature mask
    (``mode="cc"``).  ghost_axes: axes whose first and last layers are
    ghosts (self-pointers; in cc mode only where masked).  tile: optional
    (bx, by) override of `choose_tile`.  Returns (X, Y, Z) flat-id pointers
    (``-1`` for unmasked cc vertices), the same contract as `grid_steepest`
    / `grid_mask_argmax` plus the override.
    """
    if field.ndim != 3:
        raise ValueError(
            f"fused_local_phase is a 3-D kernel; got a {field.ndim}-D "
            f"field of shape {field.shape} — use the jnp init in "
            "repro.kernels.ops (impl='ref'), which dispatches it for you")
    if connectivity not in KERNEL_CONNECTIVITIES:
        raise ValueError(
            f"fused_local_phase supports 3-D connectivities "
            f"{KERNEL_CONNECTIVITIES}, got {connectivity}")
    if mode not in ("manifold", "cc"):
        raise ValueError(f"mode must be 'manifold' or 'cc', got {mode!r}")
    x, y, z = field.shape
    if id_dtype is None:
        id_dtype = jnp.int32 if field.size < 2**31 else jnp.int64
    if id_dtype == jnp.int64 and not jax.config.jax_enable_x64:
        raise ValueError("int64 pointer ids require jax_enable_x64 "
                         "(ids would silently wrap to int32)")
    bx, by = tile or choose_tile(field.shape)
    if by != y and (by % 8 or y % by):
        raise ValueError(f"tile y extent {by} must be {y} or a multiple of "
                         f"8 dividing it")

    if mode == "manifold":
        key = field
        fill = jnp.iinfo(field.dtype).min
    else:
        key = field.astype(jnp.int32)   # 0/1 mask
        fill = 0

    offsets = neighbor_offsets(3, connectivity)
    y_halo = by != y
    corners = y_halo and any(o[0] and o[1] for o in offsets)
    ny8 = y // 8
    xlo = lambda i: jnp.maximum(i * bx - 1, 0)
    xhi = lambda i: jnp.minimum((i + 1) * bx, x - 1)
    ylo = lambda j: jnp.maximum(j * (by // 8) - 1, 0)
    yhi = lambda j: jnp.minimum((j + 1) * (by // 8), ny8 - 1)
    in_specs = [pl.BlockSpec((bx, by, z), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, by, z), lambda i, j: (xlo(i), j, 0)),
                pl.BlockSpec((1, by, z), lambda i, j: (xhi(i), j, 0))]
    if y_halo:
        in_specs += [
            pl.BlockSpec((bx, 8, z), lambda i, j: (i, ylo(j), 0)),
            pl.BlockSpec((bx, 8, z), lambda i, j: (i, yhi(j), 0))]
        if corners:
            in_specs += [
                pl.BlockSpec((1, 8, z), lambda i, j: (xlo(i), ylo(j), 0)),
                pl.BlockSpec((1, 8, z), lambda i, j: (xlo(i), yhi(j), 0)),
                pl.BlockSpec((1, 8, z), lambda i, j: (xhi(i), ylo(j), 0)),
                pl.BlockSpec((1, 8, z), lambda i, j: (xhi(i), yhi(j), 0))]
    kernel = functools.partial(
        _kernel, offsets=offsets, shape=(x, y, z), bx=bx, by=by, mode=mode,
        fill=fill, ghost_axes=tuple(ghost_axes), y_halo=y_halo,
        corners=corners, id_dtype=id_dtype)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(x, bx), y // by),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bx, by, z), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((x, y, z), id_dtype),
        interpret=interpret,
        name=f"fused_local_phase_{mode}",
    )(*([key] * len(in_specs)))
