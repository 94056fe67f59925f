"""Compile-only checks for a described TPU v5e (no chip attached).

The TPU compiler ships with libtpu and compiles for a topology that is only
described, so what Mosaic or XLA would refuse on the chip — an unaligned
block, too much VMEM, an op Mosaic cannot lower — fails here at no chip
time.  Nothing runs: these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load libtpu, and under pytest-xdist every
worker imports this file.  The persistent compile cache is off around the
compiles (a described-device compile cannot be read back without a chip).
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels.fused_local_phase import fused_local_phase


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 — no libtpu / lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _largest_dense_literal(text):
    """Bytes of the largest hex-encoded dense constant in lowered text."""
    return max((len(h) // 2 for h in re.findall(r'dense<"0x([0-9A-F]*)"',
                                                 text)), default=0)


@pytest.mark.parametrize("mode, shape, ghost_axes", [
    ("manifold", (512, 512, 512), ()),          # pure 512^3 field
    ("cc", (512, 512, 512), ()),
    ("manifold", (514, 512, 512), (0,)),        # one-chip extended block
    ("cc", (258, 258, 512), (0, 1)),            # (2, 2) extended block
])
def test_fused_kernel_compiles_for_v5e(one_chip, mode, shape, ghost_axes):
    """The pointer-init kernel compiles at 512^3 — ragged x tiles, y tiles
    of 8-row multiples or the whole (non-multiple-of-8) extent — and stays
    a Mosaic kernel (tpu_custom_call) inside scoped VMEM."""
    dt = jnp.int32 if mode == "manifold" else jnp.bool_
    x = jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(lambda f: fused_local_phase(
        f, 6, mode, ghost_axes, interpret=False)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the output is the pointer array, padded only to the (8, 128) tiling
    x_, y_, z_ = shape
    tiled = 4 * x_ * (-(-y_ // 8) * 8) * (-(-z_ // 128) * 128)
    assert compiled.memory_analysis().output_size_in_bytes == tiled


def test_distributed_blocks_compile_on_2x2(topo, monkeypatch):
    """One `_manifold_block` shard_map program over the four described
    chips compiles with the kernel inside, and neither it nor the cc
    program bakes a block-sized constant (the ghost mask is built from
    iota, the global ids by arithmetic)."""
    from repro.core import make_dpc_mesh
    from repro.core.distributed import _decomp_for, _grid_program
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    grid = (64, 64, 128)
    mesh = make_dpc_mesh((2, 2), devices=topo.devices[:4])
    dec = _decomp_for(mesh, grid)
    spec = NamedSharding(mesh, P(*dec.names, None))
    coords = jax.ShapeDtypeStruct(dec.boundary_coords.shape, jnp.int32,
                                  sharding=NamedSharding(mesh, P(None, None)))
    man = _grid_program("manifold", mesh, grid, False, 6, True, "auto",
                        "replicated", 64).lower(
        jax.ShapeDtypeStruct(grid, jnp.int32, sharding=spec))
    cc = _grid_program("cc", mesh, grid, False, 6, True, "auto",
                       "replicated", 64).lower(
        jax.ShapeDtypeStruct(grid, jnp.bool_, sharding=spec), coords)
    block_bytes = int(np.prod(dec.ext))
    for lowered in (man, cc):
        assert _largest_dense_literal(lowered.as_text()) < block_bytes // 64
    compiled = man.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "all-gather" in compiled.as_text()
    ma = compiled.memory_analysis()
    per_device_block = 4 * int(np.prod(dec.local))
    assert ma.argument_size_in_bytes == per_device_block
