"""Device seconds per query under `dpc.doubling`: the local pointer
doubling, in the manifold's local phase and in each round of the cc
stitch (`bench/layers.py`)."""
import layers


def read(ctx):
    return layers.layer_seconds(ctx, "doubling")
