"""Blocking device-to-host reads per query: the `dpc.host_read` spans on
the harness's thread inside the window, over the queries.  None where the
program records no `topology.submit` span (it counts no reads)."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.n_queries:
        return None
    inside = [n for n, s, e in t.host if t.t0 <= s and e <= t.t1]
    if "topology.submit" not in inside:
        return None
    return inside.count("dpc.host_read") / ctx.n_queries
