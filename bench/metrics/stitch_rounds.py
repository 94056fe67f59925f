"""`DPCStats.stitch_rounds` of the window's last query, summed over the query's
programs (the counters repeat exactly from query to query)."""


def read(ctx):
    return ctx.counter("stitch_rounds")
