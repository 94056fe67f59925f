"""Production meshes.  Functions (not module constants) so importing never
touches jax device state.  Every mesh has Auto axes: the models place
arrays with `with_sharding_constraint`, and the DPC programs return
ordinary sharded arrays, neither of which Explicit axes accept."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_flat_mesh(mesh=None, name: str = "shards"):
    """1-D view over the same devices — the DPC slab axis."""
    if mesh is None:
        mesh = make_production_mesh()
    devices = mesh.devices.reshape(-1)
    return _mesh((devices.size,), (name,), devices=devices)


def make_block_mesh(layout, mesh=None):
    """N-D view over the same devices — the DPC block lattice.

    layout: per-axis block counts, e.g. (4, 2) or (2, 2, 2); mesh axis a
    decomposes grid axis a (axis names bx/by/bz).  Reuses the devices of
    `mesh` (default: the production mesh) so the DPC workload can share a
    pod with training jobs; total layout size must match the device count.
    """
    import math

    from repro.core import make_dpc_mesh
    if mesh is None:
        mesh = make_production_mesh()
    devices = list(mesh.devices.reshape(-1))
    layout = tuple(int(p) for p in layout)
    if math.prod(layout) != len(devices):
        raise ValueError(f"layout {layout} needs {math.prod(layout)} devices"
                         f" but mesh has {len(devices)}")
    return make_dpc_mesh(layout, devices=devices)


def make_smoke_mesh(n: int | None = None):
    """Whatever this host has (tests / examples)."""
    n = n or len(jax.devices())
    shape = (1, n) if n > 1 else (1, 1)
    return _mesh(shape, ("data", "model"))
