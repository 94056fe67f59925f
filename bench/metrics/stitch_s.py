"""Device seconds per query under `dpc.cc_stitch`: the cc local stitch's
scatters and loop, the doubling inside it left out (`bench/layers.py`)."""
import layers


def read(ctx):
    return layers.layer_seconds(ctx, "cc_stitch")
