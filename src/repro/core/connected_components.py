"""Connected components via path compression (paper Alg. 3).

Pointer init: largest masked neighbor id (incl. self); unmasked vertices are
labeled -1 and excluded.  After a first compression, sub-segments (one per
local id-maximum) are merged by the *stitch* pass
    d[d[v]] <- max over masked neighbors u of d[u]
followed by another compression.

Deviation (d) in DESIGN.md: the paper presents a single stitch+compress pass;
a chain of sub-segments whose roots only become hookable after earlier merges
requires iteration, so we run stitch+compress to a fixpoint inside a
`lax.while_loop` (<= log2 #subsegments rounds; 1-2 in practice, matching the
paper's observed behaviour).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ._table import materialize
from .pathcompress import path_compress, jump
from .steepest import graph_mask_argmax, neighbor_offsets, shift_fill


class CCResult(NamedTuple):
    labels: jax.Array      # largest vertex id of the component; -1 unmasked
    n_rounds: jax.Array    # stitch rounds executed
    n_compress_iter: jax.Array


def _grid_stitch(d: jax.Array, mask_flat: jax.Array, shape, connectivity: int,
                 sentinel: int) -> jax.Array:
    """One stitch pass (Alg. 3 lines 25-29) on a structured grid, as a
    scatter-max: for each directed neighbor pair (v, u) with both masked,
    d[d[v]] <- max(d[d[v]], d[u])."""
    d_grid = d.reshape(shape)
    m_grid = mask_flat.reshape(shape)
    out = d
    for off in neighbor_offsets(len(shape), connectivity):
        u_label = shift_fill(d_grid, off, -1).ravel()          # d[u]
        valid = mask_flat & (shift_fill(m_grid, off, False).ravel())
        tgt = jnp.where(valid, d, sentinel)                    # index d[v]
        val = jnp.where(valid, u_label, -1)
        tgt, val = materialize((tgt, val))
        out = out.at[tgt].max(val, mode="drop")
    return out


def _graph_stitch(d: jax.Array, mask: jax.Array, senders: jax.Array,
                  receivers: jax.Array, sentinel: int) -> jax.Array:
    valid = mask[senders] & mask[receivers]
    tgt = jnp.where(valid, d[senders], sentinel)
    val = jnp.where(valid, d[receivers], -1)
    return d.at[tgt].max(val, mode="drop")


def _cc_fixpoint(d0: jax.Array, stitch_fn, max_rounds: int = 64) -> CCResult:
    d, it0 = path_compress(d0)

    def cond(state):
        _, changed, r, _ = state
        return changed & (r < max_rounds)

    def body(state):
        cur, _, r, its = state
        stitched = stitch_fn(cur)
        compressed, it = path_compress(stitched)
        return (compressed, jnp.any(compressed != cur), r + jnp.int32(1),
                its + it)

    d, _, rounds, its = lax.while_loop(
        cond, body, (d, jnp.asarray(True), jnp.int32(0), it0)
    )
    return CCResult(d, rounds, its)


@partial(jax.jit, static_argnames=("connectivity", "fused_impl"))
def connected_components_grid(mask: jax.Array, connectivity: int = 6,
                              fused_impl: str = "auto") -> CCResult:
    """Mask-implicit connected components on a structured grid.

    The mask plays the paper's feature-mask role (e.g. thresholded scalar
    field); the grid is never extracted — non-feature vertices just carry -1
    (the paper's "implicitly thresholded grids", §5).  fused_impl selects
    the pointer-init implementation (repro.kernels.ops.fused_local_phase);
    labels are bit-identical across choices.
    """
    # lazy: repro.kernels imports repro.core.steepest at module load
    from repro.kernels.ops import fused_local_phase
    n = mask.size
    mask_flat = mask.ravel().astype(bool)
    d0 = fused_local_phase(mask, connectivity, mode="cc", impl=fused_impl)
    stitch = lambda d: _grid_stitch(d, mask_flat, mask.shape, connectivity, n)
    res = _cc_fixpoint(d0.ravel(), stitch)
    return CCResult(res.labels.reshape(mask.shape), res.n_rounds,
                    res.n_compress_iter)


@jax.jit
def connected_components_graph(mask: jax.Array, senders: jax.Array,
                               receivers: jax.Array) -> CCResult:
    """Mask-implicit connected components on an edge-list graph.  Pass both
    edge directions for undirected graphs.  mask=ones labels pure geometry
    (paper: CC "computed on pure geometry without any scalar data")."""
    n = mask.shape[0]
    d0 = graph_mask_argmax(mask, senders, receivers)
    stitch = lambda d: _graph_stitch(d, mask.astype(bool), senders, receivers, n)
    return _cc_fixpoint(d0, stitch)


def component_sizes(labels: jax.Array, num_segments: int | None = None):
    """Histogram of component sizes keyed by root id (unmasked dropped)."""
    flat = labels.ravel()
    # `is None`, not truthiness: an explicit num_segments=0 (empty label
    # space) must yield an empty histogram, not fall back to flat.shape[0]
    n = flat.shape[0] if num_segments is None else num_segments
    seg = jnp.where(flat >= 0, flat, n)  # park unmasked in a dropped bucket
    return jax.ops.segment_sum(
        jnp.ones_like(flat), seg, num_segments=n + 1
    )[:n]
