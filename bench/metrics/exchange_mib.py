"""MiB that the boundary table's `all_gather` moves per query:
`DPCStats.ghost_bytes` of the window's last query, the in-domain slots of
the gathered table (4 bytes a label, and 1 a mask slot where the mask is
gathered), over 2^20."""


def read(ctx):
    b = ctx.counter("ghost_bytes")
    return None if b is None else b / 2**20
