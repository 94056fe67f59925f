"""Input fields for the benchmark, made on the device.

`perlin_noise_device` is a copy of the program's generator
(`repro.data.perlin.perlin_noise_device`, the paper's §5 dataset: one layer
of Perlin noise, amplitude one), kept here so that no change to the program
can change the benchmark's inputs.  `bench/tests/test_fields.py` checks that
the two still agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

SLAB = 32           # x-planes per device call: bounds the temporaries


def _fade(t):
    return t * t * t * (t * (t * 6 - 15) + 10)


def _lattice_gradients(c):
    """Unit gradient of every lattice point in `c` ((..., ndim) int64), a
    deterministic hash of its coordinates, independent of the window."""
    ndim = c.shape[-1]
    h = np.zeros(c.shape[:-1], dtype=np.uint64)
    for d in range(ndim):
        h = h * np.uint64(0x9E3779B97F4A7C15) + c[..., d].astype(np.uint64)
    h ^= h >> np.uint64(33)
    h *= np.uint64(0xFF51AFD7ED558CCD)
    h ^= h >> np.uint64(33)
    g = []
    hh = h.copy()
    for d in range(ndim):
        g.append(np.cos(2 * np.pi * (hh % np.uint64(65536)).astype(
            np.float64) / 65536.0 + d))
        hh = (hh >> np.uint64(16)) | (hh << np.uint64(48))
    g = np.stack(g, axis=-1)
    g /= np.maximum(np.linalg.norm(g, axis=-1, keepdims=True), 1e-12)
    return g


def _perlin_slab(table, axes, lat_stride):
    """Per-vertex sum over the 2^ndim lattice corners of one slab: `axes`
    holds, per grid axis, 1-D (lattice index, offset, fade, 1 - fade)
    arrays; `table` the (lattice points, ndim) gradients, flat with strides
    `lat_stride`."""

    ndim = len(axes)

    def bcast(v, d):
        sh = [1] * ndim
        sh[d] = -1
        return v.reshape(sh)

    acc = None
    for corner in np.ndindex(*(2,) * ndim):
        flat = sum(bcast(axes[d][0] + corner[d], d) * lat_stride[d]
                   for d in range(ndim))
        # behind a barrier: fused into the gather, the index arithmetic
        # makes the TPU compiler's code generation grow with the slab
        grad = jnp.take(table, lax.optimization_barrier(flat), axis=0)
        dot = sum(grad[..., d] * bcast(axes[d][1] - corner[d], d)
                  for d in range(ndim))
        w = 1.0
        for d in range(ndim):
            w = w * bcast(axes[d][2] if corner[d] else axes[d][3], d)
        acc = dot * w if acc is None else acc + dot * w
    return acc


_perlin_slab_jit = jax.jit(_perlin_slab, static_argnums=2)


def perlin_noise_device(shape, frequency: float = 0.1, seed: int = 0,
                        origin=None):
    """Perlin noise on an integer grid of `shape`, in float32 on the
    default JAX device.  `origin` offsets the window in grid units: a block
    evaluated with its own origin holds the same values as that part of
    the whole field.  Lattice gradients and the per-axis cell, offset and
    fade terms are computed on the host; the per-vertex sum runs on the
    device in x-slabs of `SLAB` planes."""

    ndim = len(shape)
    origin = tuple(origin or (0,) * ndim)
    if seed:
        origin = tuple(o + seed * 1009 for o in origin)
    p = [(np.arange(s) + o) * frequency for s, o in zip(shape, origin)]
    cell = [np.floor(a).astype(np.int64) for a in p]
    frac = [a - c for a, c in zip(p, cell)]
    lo = [int(c.min()) for c in cell]
    lattice_axes = [np.arange(l, int(c.max()) + 2) for l, c in zip(lo, cell)]
    lat_shape = tuple(a.size for a in lattice_axes)
    lattice = np.stack(np.meshgrid(*lattice_axes, indexing="ij"), axis=-1)
    table = jnp.asarray(_lattice_gradients(lattice)
                        .reshape(-1, ndim).astype(np.float32))
    lat_stride = tuple(int(np.prod(lat_shape[d + 1:])) for d in range(ndim))
    axes = [tuple(jnp.asarray(a) for a in (
        (c - l).astype(np.int32), f.astype(np.float32),
        _fade(f).astype(np.float32), (1 - _fade(f)).astype(np.float32)))
        for c, l, f in zip(cell, lo, frac)]
    parts = []
    for x0 in range(0, shape[0], SLAB):
        sl = slice(x0, min(x0 + SLAB, shape[0]))
        parts.append(_perlin_slab_jit(table, [tuple(a[sl] for a in axes[0])] + axes[1:],
                        lat_stride))
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


def make_field(config, mesh):
    """The configuration's float32 field (its window of the noise starts
    at `origin`), laid out as the mesh's block decomposition expects: each
    block is generated on the device that owns it (mesh axis a splits grid
    axis a)."""

    grid = tuple(config["grid"])
    layout = tuple(config["layout"])
    if any(g % p for g, p in zip(grid, layout)):
        raise ValueError(f"layout {layout} does not divide grid {grid}")
    local = tuple(g // p for g, p in zip(grid, layout)) + grid[len(layout):]
    base = tuple(config["origin"])
    blocks = []
    for idx in np.ndindex(*layout):
        off = [i * n for i, n in zip(idx, local)]
        org = tuple(b + o for b, o in zip(base, off + [0] * len(grid)))
        dev = mesh.devices[idx]
        with jax.default_device(dev):
            blocks.append(jax.device_put(perlin_noise_device(
                local, config["frequency"], 0, org), dev))
    names = mesh.axis_names
    spec = P(*names, *([None] * (len(grid) - len(names))))
    return jax.make_array_from_single_device_arrays(
        grid, NamedSharding(mesh, spec), blocks)
