"""Morse-Smale segmentation via path compression (paper §4.2).

The descending manifold maps every vertex to the maximum its steepest-ascent
integral line terminates in; the ascending manifold symmetrically to minima.
Their product partitions the domain into the MS segmentation (the "fast
preview" of the MS complex of Maack et al. [33]).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .pathcompress import path_compress
from .steepest import grid_steepest, graph_steepest


class MSSegmentation(NamedTuple):
    ascending: jax.Array    # flat vertex id of the reached minimum
    descending: jax.Array   # flat vertex id of the reached maximum
    segmentation: jax.Array # injective hash of the (asc, desc) pair
    n_iter_asc: jax.Array
    n_iter_desc: jax.Array


def _fused_init(order, connectivity, fused_impl):
    """Pointer init through the kernels dispatch (lazy import:
    repro.kernels imports repro.core.steepest at module load); bit-identical
    to the plain grid_steepest init on every implementation."""
    from repro.kernels.ops import fused_local_phase
    return fused_local_phase(order, connectivity, mode="manifold",
                             impl=fused_impl).ravel()


def descending_manifold(order: jax.Array, connectivity: int = 6,
                        fused_impl: str = "auto"):
    return path_compress(_fused_init(order, connectivity, fused_impl))


def ascending_manifold(order: jax.Array, connectivity: int = 6,
                       fused_impl: str = "auto"):
    # ascending = descending on the flipped order field (the kernel argmax
    # of size-1-order targets exactly grid_steepest's descending=False
    # choice: a monotone transform with unique values preserves the argmax)
    return path_compress(_fused_init(order.size - 1 - order, connectivity,
                                     fused_impl))


@partial(jax.jit, static_argnums=2)
def _pair_hash(desc, asc, n):
    """Injective (desc, asc) -> segment id when n*n fits the id dtype; for
    larger grids consume the (ascending, descending) pair directly."""
    dt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    with jax.named_scope("dpc.segmentation"):
        return desc.astype(dt) * n + asc.astype(dt)


def ms_segmentation(order: jax.Array, connectivity: int = 6,
                    fused_impl: str = "auto") -> MSSegmentation:
    desc, it_d = descending_manifold(order, connectivity, fused_impl)
    asc, it_a = ascending_manifold(order, connectivity, fused_impl)
    seg = _pair_hash(desc, asc, order.size)
    return MSSegmentation(asc.reshape(order.shape), desc.reshape(order.shape),
                          seg.reshape(order.shape), it_a, it_d)


def ms_segmentation_graph(order: jax.Array, senders: jax.Array,
                          receivers: jax.Array, connectivity: int = 0
                          ) -> MSSegmentation:
    """Unstructured variant: edges as (senders, receivers) index lists."""
    del connectivity
    d0 = graph_steepest(order, senders, receivers, descending=True)
    desc, it_d = path_compress(d0)
    a0 = graph_steepest(order, senders, receivers, descending=False)
    asc, it_a = path_compress(a0)
    seg = _pair_hash(desc, asc, order.shape[0])
    return MSSegmentation(asc, desc, seg, it_a, it_d)


def extrema(order: jax.Array, connectivity: int = 6):
    """(maxima_mask, minima_mask): vertices that are their own steepest target."""
    n = order.size
    idx = jnp.arange(n, dtype=jnp.int32)
    maxima = grid_steepest(order, connectivity, descending=True) == idx
    minima = grid_steepest(order, connectivity, descending=False) == idx
    return maxima.reshape(order.shape), minima.reshape(order.shape)
