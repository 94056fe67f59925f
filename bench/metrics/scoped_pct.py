"""Share of the window's device self time that lies under some `dpc.`
scope of the program (`bench/layers.py`); 0 where the program names no
layer.  The rest is the harness's own programs and unnamed ops."""
import layers


def read(ctx):
    if ctx.trace is None:
        return None
    s = layers.split(ctx.trace, layers.op_paths())
    total = sum(s.values())
    if total <= 0:
        return None
    return 100.0 * (total - s.get(None, 0.0)) / total
