"""The layer names that a profiler trace of a query carries.

Device work is named with `jax.named_scope` (`dpc.<layer>[.<part>]`), which
reaches the compiled program's op metadata (`op_name`), and from there the
`tf_op` of every device op in the trace; host work with
`jax.profiler.TraceAnnotation` spans (`topology.submit`, `dpc.dispatch`,
`dpc.host_read`).  The benchmark's per-layer readers (`bench/layers.py`)
count on both, so a refactor that drops a scope or a span fails here.
One CPU device, a (1,) mesh, tiny grids; the (2, 2) mesh, where every
collective has to lie under `dpc.exchange`, in a subprocess with 4 virtual
CPU devices (the device-count flag is never set in the test process).
"""
import json
import os
import re
import subprocess
import sys
import textwrap
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

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

GRID = (8, 6, 4)
N = int(np.prod(GRID))
LOCAL = {"dpc.halo", "dpc.init", "dpc.doubling", "dpc.ids"}
COLLECTIVE = re.compile(r"^(all-gather|collective-permute|all-reduce)")
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


_MESH_2X2 = textwrap.dedent(r"""
    import json, os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    from repro.core import make_dpc_mesh
    from repro.core.distributed import _decomp_for, _grid_program

    grid = (8, 6, 4)
    mesh = make_dpc_mesh((2, 2))
    out = {}
    for kind in ("manifold", "cc"):
        for mode in ("replicated", "sharded"):
            if kind == "manifold":
                args = (jnp.arange(192, dtype=jnp.int32).reshape(grid),)
            else:
                args = (jnp.ones(grid, bool),
                        _decomp_for(mesh, grid).boundary_coords_dev)
            prog = _grid_program(kind, mesh, grid, False, 6, True, "auto",
                                 mode, 64)
            text = prog.lower(*args).compile().as_text()
            # (opcode, op_name or "") of every instruction
            ops = []
            for line in text.splitlines():
                op = re.search(r"= [^=]*? ([a-z][\w-]*)\(", line)
                name = re.search(r'op_name="([^"]*)"', line)
                if op:
                    ops.append((op.group(1), name.group(1) if name else ""))
            out[f"{kind}.{mode}"] = ops
    print("RESULT " + json.dumps(out))
""")


def innermost_dpc(op_name: str):
    """The innermost `dpc.` component of an op name, whole (the layer
    rule of `bench/layers.py` takes it up to its first dot)."""
    found = [p for p in op_name.split("/") if p.startswith("dpc.")]
    return found[-1] if found else None


@pytest.fixture(scope="module")
def ops_2x2():
    env = dict(os.environ, PYTHONPATH=_SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MESH_2X2], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(x for x in proc.stdout.splitlines()
                if x.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("table_mode", ["replicated", "sharded"])
@pytest.mark.parametrize("kind", ["manifold", "cc"])
def test_2x2_collectives_are_the_exchange(ops_2x2, kind, table_mode):
    """On a (2, 2) mesh every collective (the halo and table `ppermute`s,
    the table's `all_gather`, the `pmax`/`pmin`/`psum` of the convergence
    checks and of `DPCStats`) has `dpc.exchange` as its innermost `dpc.`
    scope, and every layer scope of the one-chip program is still there."""
    ops = ops_2x2[f"{kind}.{table_mode}"]
    coll = [(op, name) for op, name in ops if COLLECTIVE.match(op)]
    kinds = {COLLECTIVE.match(op).group(1) for op, _ in coll}
    want_kinds = {"collective-permute", "all-reduce"}
    if table_mode == "replicated":
        want_kinds.add("all-gather")
    assert want_kinds <= kinds, f"collectives missing: {want_kinds - kinds}"
    stray = [(op, name) for op, name in coll
             if innermost_dpc(name) != "dpc.exchange"]
    assert not stray, f"collectives outside dpc.exchange: {stray}"
    found = scopes_in(name for _, name in ops)
    want = (SCOPES[kind] - ABSENT.get((kind, table_mode), set())
            | {"dpc.exchange"})
    assert want <= found, f"scopes missing: {sorted(want - found)}"
    if table_mode == "sharded":
        assert "dpc.table" in found


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
