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



def test_one_chip_cc_program_compiles_at_512(topo, monkeypatch):
    """The one-chip cc program at 512^3 (the benchmark's cc query, the value
    search a sort-merge join in 64 chunks) compiles for a v5e with the
    kernel inside.  The chip's peak memory counts the program's machine
    code, so the join's code must stay within the 1% bound of the cell's
    peak; its temporaries are set by the stitch loop, not the join."""
    from repro.core import make_dpc_mesh
    from repro.core.distributed import _decomp_for, _grid_program
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    grid = (512, 512, 512)
    mesh = make_dpc_mesh((1,), devices=topo.devices[:1])
    dec = _decomp_for(mesh, grid)
    coords = jax.ShapeDtypeStruct(dec.boundary_coords.shape, jnp.int32,
                                  sharding=NamedSharding(mesh, P(None, None)))
    mask = jax.ShapeDtypeStruct(
        grid, jnp.bool_, sharding=NamedSharding(mesh, P(*dec.names, None,
                                                        None)))
    compiled = _grid_program("cc", mesh, grid, False, 6, True, "auto",
                             "replicated", 64).lower(mask, coords).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    # with the binary search: 47848448 bytes of code, 7011358720 of
    # temporaries; the cell's peak on the chip, 1805908992 bytes
    assert ma.generated_code_size_in_bytes - 47848448 < 0.01 * 1805908992
    # the join's program reads 290304 bytes more temporaries, the
    # compiler's async-copy descriptors: within the same 1%
    assert ma.temp_size_in_bytes <= 7011358720 * 1.01


def test_value_search_holds_less_than_binary_search(one_chip):
    """At the one-chip 512^3 shape (2^27 owned labels, 2 x 512^2 slots) the
    join's temporaries stay under the binary search's: the chunks keep its
    sorts a few tens of MB."""
    from repro.core import _table
    labels = jax.ShapeDtypeStruct((512 ** 3,), jnp.int32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((2 * 512 ** 2,), jnp.int32,
                                 sharding=one_chip)
    compiled = jax.jit(_table.value_substitute).lower(
        labels, labels, table, table).compile()
    # jnp.searchsorted's substitution read 2147613184 bytes (four labels
    # arrays); one labels array is 536870912
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 536870912 + 64 * 2 ** 20
