"""The cc stitch takes each vertex's maximum over the stencil and scatters
once per round; the per-offset scatter loop it replaced is kept here as the
reference.  Both give bit-identical labels, the same stitch rounds and the
same doubling rounds, on the pure backend and through
`distributed_connected_components` (in a subprocess with 8 host devices:
the main test process keeps its single-device view).
"""
import inspect
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core._table import materialize
from repro.core.connected_components import _cc_fixpoint, grid_stitch
from repro.core.distributed import _cc_local_fixpoint
from repro.core.steepest import neighbor_offsets, shift_fill
from repro.data import perlin_noise
from repro.kernels.ops import fused_local_phase

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def per_offset_stitch(d, mask, connectivity, sentinel, read_out=True):
    """The replaced stitch: one scatter-max per neighbor offset.  With
    `read_out` each offset takes its targets from the previous offset's
    output (the distributed backend's loop), else from the round's input
    (the pure backend's)."""
    out = d
    dg = d.reshape(mask.shape)
    m = mask.ravel()
    for off in neighbor_offsets(mask.ndim, connectivity):
        u_label = shift_fill(dg, off, -1).ravel()
        valid = m & shift_fill(mask, off, False).ravel() & (u_label >= 0)
        tgt, val = materialize((jnp.where(valid, out if read_out else d,
                                          sentinel),
                                jnp.where(valid, u_label, -1)))
        out = out.at[tgt].max(val, mode="drop")
    return out


def _top10(shape):
    f = perlin_noise(shape, frequency=0.1, seed=3)
    return f >= np.quantile(f, 0.9)


@pytest.mark.parametrize("shape, connectivity", [
    ((96, 96, 96), 6),
    ((96, 96, 96), 18),
    ((64, 64, 64), 26),
    ((37, 23, 19), 14),
])
def test_pure_stitch_matches_per_offset_loop(shape, connectivity):
    """The pure backend's stitch gives the per-offset loop's labels, stitch
    rounds and doubling rounds."""
    mask = jnp.asarray(_top10(shape))
    n = mask.size

    def run(mask, use_reference):
        d0 = fused_local_phase(mask, connectivity, mode="cc").ravel()
        if use_reference:
            stitch = lambda d: per_offset_stitch(
                d, mask, connectivity, n, read_out=False)
        else:
            stitch = lambda d: grid_stitch(d, mask, connectivity, n)
        return _cc_fixpoint(d0, stitch)

    run = jax.jit(run, static_argnums=1)
    got, ref = run(mask, False), run(mask, True)
    assert np.array_equal(np.asarray(got.labels), np.asarray(ref.labels))
    assert int(got.n_rounds) == int(ref.n_rounds)
    assert int(got.n_compress_iter) == int(ref.n_compress_iter)


_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import distributed, make_dpc_mesh
    from repro.core._table import materialize
    from repro.core.steepest import neighbor_offsets, shift_fill
    from repro.data import perlin_noise
    REFERENCE

    shape, layout, connectivity = eval(sys.argv[1])
    f = perlin_noise(shape, frequency=0.1, seed=3)
    mask = jnp.asarray(f >= np.quantile(f, 0.9))
    mesh = make_dpc_mesh(layout, devices=jax.devices()[:int(np.prod(layout))])

    def run():
        labels, st = distributed.distributed_connected_components(
            mask, mesh, connectivity)
        return (np.asarray(labels), int(st.stitch_rounds),
                int(st.local_iters), float(st.pad_fraction))

    got = run()
    distributed.grid_stitch = per_offset_stitch
    distributed._grid_program.cache_clear()
    ref = run()
    assert np.array_equal(got[0], ref[0]), "labels differ"
    assert got[1:] == ref[1:], (got[1:], ref[1:])
    if any(s % k for s, k in zip(shape, layout)):
        assert got[3] > 0, "the ragged grid was not padded"
    print("STITCH-OK", got[1:])
""").replace("REFERENCE", inspect.getsource(per_offset_stitch))


@pytest.mark.parametrize("shape, layout, connectivity", [
    ((96, 96, 96), (1,), 6),
    ((96, 96, 96), (1,), 18),
    ((64, 64, 64), (1,), 26),
    ((128, 128, 64), (2, 2), 6),
    ((97, 61, 43), (2, 2, 2), 6),      # ragged: pad-and-mask blocks
])
def test_distributed_stitch_matches_per_offset_loop(shape, layout,
                                                    connectivity):
    """Through `distributed_connected_components`, the one-scatter stitch
    gives the per-offset loop's labels bit for bit, and the same
    `stitch_rounds` and `local_iters`."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER, repr((shape, layout, connectivity))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "STITCH-OK" in proc.stdout


@pytest.mark.parametrize("connectivity", [6, 26])
def test_stitch_lowers_to_one_scatter(connectivity):
    """A stitch round holds one scatter whatever the stencil, and so does
    the whole local cc fixpoint (its doubling only gathers)."""
    mask = jax.ShapeDtypeStruct((10, 10, 12), jnp.bool_)
    d = jax.ShapeDtypeStruct((10 * 10 * 12,), jnp.int32)
    stitch = jax.jit(lambda d, m: grid_stitch(d, m, connectivity, d.size))
    fixpoint = jax.jit(lambda d, m: _cc_local_fixpoint(d, m, connectivity))
    for fn in (stitch, fixpoint):
        assert fn.lower(d, mask).as_text().count('"stablehlo.scatter"') == 1
