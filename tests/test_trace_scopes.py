"""The layer names that a profiler trace of a query carries.

Device work is named with `jax.named_scope` (`dpc.<layer>[.<part>]`), which
reaches the compiled program's op metadata (`op_name`), and from there the
`tf_op` of every device op in the trace; host work with
`jax.profiler.TraceAnnotation` spans (`topology.submit`, `dpc.dispatch`,
`dpc.host_read`).  The benchmark's per-layer readers (`bench/layers.py`)
count on both, so a refactor that drops a scope or a span fails here.
One CPU device, a (1,) mesh, tiny grids.
"""
import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.core import compute_order, make_dpc_mesh
from repro.core.distributed import _decomp_for, _flip_order, _grid_program
from repro.core.ms_segmentation import _pair_hash
from repro.topology import TopologyRequest, submit

GRID = (8, 6, 4)
N = int(np.prod(GRID))
LOCAL = {"dpc.halo", "dpc.init", "dpc.doubling", "dpc.ids"}
TABLE = {"dpc.table.gather", "dpc.table.substitute"}
SCOPES = {
    "manifold": LOCAL | TABLE | {"dpc.table.chase"},
    "cc": LOCAL | TABLE | {"dpc.cc_stitch", "dpc.table.chase",
                           "dpc.table.propagate"},
}
# the sharded cc table floods without a chase (DESIGN.md §Table-sharding)
ABSENT = {("cc", "sharded"): {"dpc.table.chase"}}


def op_names(compiled) -> list:
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def scopes_in(names) -> set:
    """Every path component of the op names."""
    return {part for n in names for part in n.split("/")}


def _args(kind, mesh):
    if kind == "manifold":
        return (jnp.arange(N, dtype=jnp.int32).reshape(GRID),)
    return (jnp.ones(GRID, bool), _decomp_for(mesh, GRID).boundary_coords_dev)


@pytest.mark.parametrize("table_mode", ["replicated", "sharded"])
@pytest.mark.parametrize("kind", ["manifold", "cc"])
def test_grid_program_names_each_layer(kind, table_mode):
    mesh = make_dpc_mesh((1,))
    prog = _grid_program(kind, mesh, GRID, False, 6, True, "auto",
                         table_mode, 64)
    names = op_names(prog.lower(*_args(kind, mesh)).compile())
    found = scopes_in(names)
    want = SCOPES[kind] - ABSENT.get((kind, table_mode), set())
    assert want <= found, f"scopes missing: {sorted(want - found)}"
    if table_mode == "sharded":
        assert "dpc.table" in found
    # the cc stitch's loop holds the doubling of each round
    if kind == "cc":
        assert any(re.search(r"/dpc\.cc_stitch/(.*/)?dpc\.doubling/", n)
                   for n in names)


@pytest.mark.parametrize("fn, args, scope", [
    (jax.jit(compute_order), (jnp.zeros(GRID, jnp.float32),), "dpc.order"),
    (_flip_order, (jnp.zeros(GRID, jnp.int32), N), "dpc.order.flip"),
    (_pair_hash, (jnp.zeros(GRID, jnp.int32), jnp.zeros(GRID, jnp.int32),
                  N), "dpc.segmentation"),
], ids=["compute_order", "flip_order", "pair_hash"])
def test_query_programs_outside_the_grid_program_are_scoped(fn, args,
                                                            scope):
    statics = [a for a in args if isinstance(a, int)]
    arrays = [a for a in args if not isinstance(a, int)]
    compiled = fn.lower(*arrays, *statics).compile()
    assert scope in scopes_in(op_names(compiled))


def _host_events(trace_dir):
    path, = Path(trace_dir).glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [e.name for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("query, programs", [("cc", 1), ("ms", 2)])
def test_query_host_spans(tmp_path, query, programs):
    """One host read per `check_converged` and one per `DPCStats` field
    (10), for each grid program the query runs."""
    mesh = make_dpc_mesh((1,))
    field = jax.random.normal(jax.random.key(0), GRID)
    if query == "cc":
        req = TopologyRequest("cc", backend="distributed", mesh=mesh,
                              mask=field > 0.5)
    else:
        req = TopologyRequest("ms", backend="distributed", mesh=mesh,
                              order=compute_order(field))
    jax.block_until_ready(submit(req).labels)        # compiles outside
    with jax.profiler.trace(str(tmp_path)):
        res = submit(req)
        jax.block_until_ready((res.labels, res.segmentation))
    names = _host_events(tmp_path)
    assert names.count("topology.submit") == 1
    assert names.count("dpc.dispatch") == programs
    assert names.count("dpc.host_read") == programs * 11
