"""The one shard_map wrapper: every SPMD entry point in this repo goes
through `shard_map_norep`, which turns off the replication check
(`check_vma`) — the per-device stats and table stacks are not provably
replicated even where they are."""
from __future__ import annotations

import jax


def shard_map_norep(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
