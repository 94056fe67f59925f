"""Device seconds per query under `dpc.order`: `compute_order`'s key,
sort and scatter, and the ascending manifold's order flip
(`bench/layers.py`).  Unlike `order_s`, nothing blocks the host for it."""
import layers


def read(ctx):
    return layers.layer_seconds(ctx, "order")
