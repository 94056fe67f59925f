"""Share of the traced window in which no operation ran on the chips
(1 - busy / window, busy averaged over the cell's chips)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
