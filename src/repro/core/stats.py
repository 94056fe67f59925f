"""Unified DPC run statistics for both distributed backends.

`DPCStats` (structured block lattice) and `GraphDPCStats` (unstructured
vertex partitions) report the SAME seven fields in the SAME order, so the
serving layer and the benchmarks can consume either through one code path:
shared fields first (`local_iters`, `table_iters`, `stitch_rounds`,
`ghost_bytes`, `masked_ghost_fraction`, `pad_fraction`, `comm_phases`).
Both expose `as_dict()`, the host-side uniform reporting hook — values are
converted to python scalars (or lists, for the batched entry points whose
stats carry a leading request dim), never jax arrays.

The classes stay distinct NamedTuples (not one shared class) on purpose:
each is an output pytree of its backend's `shard_map` and is constructed
per-device under tracing; keeping them separate lets a backend grow a
backend-specific trailing field later without perturbing the shared prefix
the serving layer keys on.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax

# the shared field prefix, in the canonical order both classes use
STAT_FIELDS = ("local_iters", "table_iters", "stitch_rounds", "ghost_bytes",
               "masked_ghost_fraction", "pad_fraction", "comm_phases",
               "table_bytes_peak",
               "exchange_rounds", "converged")


def host_read(x) -> np.ndarray:
    """`np.asarray` of a device value: a blocking device-to-host read,
    recorded as one `dpc.host_read` span in the profiler trace."""
    with jax.profiler.TraceAnnotation("dpc.host_read"):
        return np.asarray(x)


def stats_as_dict(stats) -> dict:
    """Host-side uniform view of any *DPCStats NamedTuple: python scalars
    (0-d) or lists (batched stats with a leading request dim)."""
    out = {}
    for name, val in zip(stats._fields, stats):
        a = host_read(val)
        out[name] = a.item() if a.ndim == 0 else a.tolist()
    return out


class DPCStats(NamedTuple):
    """Per-run statistics of the structured (block-lattice) backend."""
    local_iters: jax.Array      # pointer-doubling rounds in the local phase
    table_iters: jax.Array      # rounds on the gathered ghost table
    stitch_rounds: jax.Array    # CC only (0 for MS)
    ghost_bytes: jax.Array      # in-domain bytes all-gathered (the ONE comm
                                # phase; pad slots excluded, deviation (p))
    masked_ghost_fraction: jax.Array  # CC: fraction of boundary actually
                                      # masked (over in-domain slots)
    pad_fraction: jax.Array     # fraction of block cells that are padding
                                # (0 whenever the layout divides the grid)
    comm_phases: jax.Array      # bulk exchange phases traced (paper budget:
                                # 1; the halo ppermute is ghost setup, not a
                                # gather phase)
    table_bytes_peak: jax.Array    # per-device bytes materialized for the
                                   # boundary-table resolution (replicated:
                                   # the full gathered table; sharded: own
                                   # faces + halo stack, deviation (s))
    exchange_rounds: jax.Array     # sharded mode: outer halo-exchange rounds
                                   # of the table fixpoint (0 = replicated)
    converged: jax.Array           # 1 iff every table fixpoint reached its
                                   # fixed point within max_iter (a 0 here
                                   # raises eagerly; see _table.check_converged)

    def as_dict(self) -> dict:
        return stats_as_dict(self)


class GraphDPCStats(NamedTuple):
    """Per-run statistics of the unstructured (vertex-partition) backend.
    Field names/order mirror `DPCStats` exactly (see module docstring)."""
    local_iters: jax.Array      # pointer-doubling rounds in the local phase
    table_iters: jax.Array      # chase + propagate rounds on the cut table
    stitch_rounds: jax.Array    # local stitch fixpoint rounds
    ghost_bytes: jax.Array      # real cut bytes all-gathered (the ONE comm
                                # phase; pad slots excluded, deviation (p))
    masked_ghost_fraction: jax.Array  # fraction of REAL cut slots masked
    pad_fraction: jax.Array     # fraction of owned slots that are padding
                                # (0 for a balanced partition)
    comm_phases: jax.Array      # all_gather phases traced (paper budget: 1)
    table_bytes_peak: jax.Array    # per-device bytes materialized for the
                                   # cut-table resolution (replicated: full
                                   # gathered table (+mask); sharded: own
                                   # row + neighbor halo, deviation (s))
    exchange_rounds: jax.Array     # sharded mode: outer halo-exchange rounds
                                   # of the cut fixpoint (0 = replicated)
    converged: jax.Array           # 1 iff every table fixpoint reached its
                                   # fixed point within max_iter

    def as_dict(self) -> dict:
        return stats_as_dict(self)


assert DPCStats._fields == STAT_FIELDS == GraphDPCStats._fields
