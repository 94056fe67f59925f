"""Perlin noise (Perlin [39]) — the paper's synthetic scaling dataset:
"one layer of Perlin Noise with an amplitude of one and frequency in every
dimension of 0.1" (§5).  Gradient-lattice implementation in pure numpy/jnp so
the same field can be regenerated shard-locally at any resolution (weak
scaling) without materialising the global grid on one host.
"""
from __future__ import annotations

import numpy as np


def _fade(t):
    return t * t * t * (t * (t * 6 - 15) + 10)


def _lattice_gradients(c):
    """Unit gradient of every lattice point in `c` ((..., ndim) int64),
    a deterministic hash of its coordinates — independent of the window."""
    ndim = c.shape[-1]
    h = np.zeros(c.shape[:-1], dtype=np.uint64)
    for d in range(ndim):
        h = h * np.uint64(0x9E3779B97F4A7C15) + c[..., d].astype(np.uint64)
    h ^= h >> np.uint64(33)
    h *= np.uint64(0xFF51AFD7ED558CCD)
    h ^= h >> np.uint64(33)
    # map hash to a unit-ish gradient via ndim angles
    g = []
    hh = h.copy()
    for d in range(ndim):
        g.append(np.cos(2 * np.pi * (hh % np.uint64(65536)).astype(
            np.float64) / 65536.0 + d))
        hh = (hh >> np.uint64(16)) | (hh << np.uint64(48))
    g = np.stack(g, axis=-1)
    g /= np.maximum(np.linalg.norm(g, axis=-1, keepdims=True), 1e-12)
    return g


def perlin_noise(shape, frequency: float = 0.1, seed: int = 0,
                 origin=None) -> np.ndarray:
    """N-D Perlin noise on an integer grid of `shape`, amplitude ~1.

    `origin` offsets the sample window in lattice units — shards evaluate
    their own slab with origin=(x0, 0, 0) and obtain bit-identical values to
    the global field (the lattice gradients are seeded by cell coordinate
    hashes, not by array position).
    """
    ndim = len(shape)
    origin = tuple(origin or (0,) * ndim)
    coords = np.meshgrid(*[
        (np.arange(s) + o) * frequency for s, o in zip(shape, origin)
    ], indexing="ij")
    pts = np.stack(coords, axis=-1)             # (*shape, ndim)
    cell = np.floor(pts).astype(np.int64)       # lattice cell of each point
    frac = pts - cell

    corners = list(np.ndindex(*(2,) * ndim))
    u = _fade(frac)
    acc = None
    for corner in corners:
        grad = _lattice_gradients(cell + np.array(corner))
        disp = frac - np.array(corner)
        dot = np.sum(grad * disp, axis=-1)
        w = np.ones(dot.shape)
        for d in range(ndim):
            w = w * (u[..., d] if corner[d] else (1 - u[..., d]))
        acc = dot * w if acc is None else acc + dot * w

    # seed folds into the lattice origin so different seeds decorrelate
    if seed:
        return perlin_noise(shape, frequency, 0,
                            tuple(o + seed * 1009 for o in origin))
    return acc.astype(np.float32)


def _perlin_slab(table, axes, lat_stride):
    """Device half of `perlin_noise_device` for one slab: `axes` holds, per
    grid axis, 1-D (lattice index, offset, fade, 1 - fade) arrays; `table`
    the (lattice points, ndim) gradients, flat with strides `lat_stride`."""
    import jax.numpy as jnp
    from jax import lax

    ndim = len(axes)

    def bcast(v, d):
        sh = [1] * ndim
        sh[d] = -1
        return v.reshape(sh)

    acc = None
    for corner in np.ndindex(*(2,) * ndim):
        flat = sum(bcast(axes[d][0] + corner[d], d) * lat_stride[d]
                   for d in range(ndim))
        # materialised: fused into the gather, the index arithmetic makes
        # the TPU compiler's code generation grow with the slab
        grad = jnp.take(table, lax.optimization_barrier(flat), axis=0)
        dot = sum(grad[..., d] * bcast(axes[d][1] - corner[d], d)
                  for d in range(ndim))
        w = 1.0
        for d in range(ndim):
            w = w * bcast(axes[d][2] if corner[d] else axes[d][3], d)
        acc = dot * w if acc is None else acc + dot * w
    return acc


def perlin_noise_device(shape, frequency: float = 0.1, seed: int = 0,
                        origin=None):
    """`perlin_noise` evaluated on the default JAX device, in float32.

    The same formula: the lattice gradients come from `_lattice_gradients`
    on the host (one per lattice point of the window — a few hundred
    thousand at 512^3 and frequency 0.1), the per-axis cell, offset and
    fade terms in float64 on the host, and only the per-vertex sum over the
    2^ndim corners runs on the device, in float32 (so values agree with
    `perlin_noise` to float32 rounding, not bit for bit).  The field is
    built in x-slabs of 32 planes to bound device temporaries.
    """
    import jax
    import jax.numpy as jnp

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
    fn = jax.jit(_perlin_slab, static_argnums=2)
    parts = []
    slab = 32
    for x0 in range(0, shape[0], slab):
        sl = slice(x0, min(x0 + slab, shape[0]))
        parts.append(fn(table, [tuple(a[sl] for a in axes[0])] + axes[1:],
                        lat_stride))
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
