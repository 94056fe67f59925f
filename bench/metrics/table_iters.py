"""`DPCStats.table_iters` of the window's last query: the chase and
hook-propagate rounds over the gathered boundary table (the counters repeat
exactly from query to query)."""


def read(ctx):
    return ctx.counter("table_iters")
