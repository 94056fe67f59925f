"""Pallas TPU kernel: in-VMEM block path compression.

TPU adaptation of the paper's thread-local compression: right after the
steepest init every pointer targets a direct neighbor, so the first K
doubling rounds stay almost entirely inside an x-slab.  Running those rounds
on a VMEM-resident tile costs one HBM read + one write for K rounds, versus
K full HBM round-trips for global `d <- d[d]` gathers (each of which moves
8 bytes/vertex/round at 819 GB/s).  Out-of-block and negative pointers are
fixed points, exactly like ghost vertices in Alg. 1 — the block boundary IS
a ghost boundary, so correctness follows from the same argument as the
distributed algorithm, and the remaining global rounds finish the job.

Arrays whose length does not divide the tile size take a ceil-division
grid: the input is padded up to it with the sentinel -1, which the kernel
treats as a fixed point, so the clamped last tile never reads past the
ragged extent (pad-and-mask, deviation (p) in DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(d_ref, out_ref, *, rounds, block):
    i = pl.program_id(0)
    base = i * block
    d = d_ref[...]
    for _ in range(rounds):
        local = d - base
        in_block = (d >= 0) & (local >= 0) & (local < block)
        nd = jnp.take(d, jnp.clip(local, 0, block - 1), axis=0)
        d = jnp.where(in_block, nd, d)
    out_ref[...] = d


def _next_pow2(n: int) -> int:
    """Engine bucket capacity (serve.bucketing.next_pow2, re-derived here to
    keep kernels import-independent of the serving layer)."""
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


@functools.partial(jax.jit,
                   static_argnames=("rounds", "block", "interpret"))
def _padded_call(d: jax.Array, rounds: int, block: int,
                 interpret: bool) -> jax.Array:
    """The jitted pallas program over an already-bucketed length: its cache
    keys on (capacity, block, rounds, dtype) only."""
    n = d.shape[0]
    n_tiles = -(-n // block)          # ceil: the last tile may be ragged
    n_pad = n_tiles * block
    if n_pad != n:
        d = jnp.pad(d, (0, n_pad - n), constant_values=-1)
    kernel = functools.partial(_kernel, rounds=rounds, block=block)
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), d.dtype),
        interpret=interpret,
    )(d)
    return out[:n] if n_pad != n else out


def block_pathcompress(d: jax.Array, rounds: int = 4, block: int = 4096,
                       *, interpret: bool) -> jax.Array:
    """K pointer-doubling rounds confined to `block`-sized tiles.

    d: (N,) int32 global pointers (any N; ragged tiles are padded with the
    -1 sentinel and sliced back off).  The length is snapped to the serving
    engine's power-of-two bucket capacities OUTSIDE the jit boundary —
    `min(block, n)` used to bake the raw request length into the traced
    shape, so every distinct length compiled a fresh executable; now any n
    in (cap/2, cap] reuses one per-(capacity, block, dtype) executable, at
    the cost of at most one extra tile's worth of inert -1 work.
    """
    n = d.shape[0]
    cap = _next_pow2(n)
    block = min(block, cap)
    if cap != n:
        d = jnp.pad(d, (0, cap - n), constant_values=-1)
    out = _padded_call(d, rounds, block, interpret)
    return out[:n] if cap != n else out
