"""Device seconds per query under `dpc.table.*`: the boundary table's
gather, chase, propagation and substitution (`bench/layers.py`)."""
import layers


def read(ctx):
    return layers.layer_seconds(ctx, "table")
