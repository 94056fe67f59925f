"""Device seconds per query under `dpc.exchange`: every collective of the
grid program (the halo `ppermute`s, the boundary table's `all_gather`, the
`pmax`/`psum` of the convergence checks and of `DPCStats`), averaged over
the cell's chips (`bench/layers.py`).  A collective's device time holds
its transfer and the wait for the slowest chip.  None where no op of the
window is under that scope (a program that does not name it)."""
import layers


def read(ctx):
    if ctx.trace is None or not ctx.n_queries:
        return None
    s = layers.split(ctx.trace, layers.op_paths())
    if "exchange" not in s:
        return None
    return s["exchange"] / ctx.n_queries
