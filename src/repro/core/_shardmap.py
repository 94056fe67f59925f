"""The SPMD helpers.  Every SPMD entry point in this repo goes through
`shard_map_norep`, which turns off the replication check (`check_vma`) — the
per-device stats and table stacks are not provably replicated even where
they are.  Every collective of the distributed programs runs through
`collective`, which names it as the exchange layer of the device trace."""
from __future__ import annotations

import jax


def shard_map_norep(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def collective(op, *args, **kwargs):
    """`op(*args, **kwargs)` (a `lax.ppermute`, `all_gather`, `pmax`, ...)
    under `jax.named_scope("dpc.exchange")`, the innermost `dpc.` scope of
    its ops: transfers between chips, and the waits for the slowest one,
    count as the exchange wherever they are called."""
    with jax.named_scope("dpc.exchange"):
        return op(*args, **kwargs)
