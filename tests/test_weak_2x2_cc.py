"""Top-10% connected components of a Perlin field on a (2, 2) block mesh,
the benchmark's weak-scaled deployment at small grids: each block is not a
cube, the noise's window starts at a non-zero origin, the boundary table is
replicated.  Labels are compared bit for bit with the BFS oracle, and the
table's counters with the decomposition's geometry.

One subprocess with 4 virtual CPU devices runs every case (the dry-run
rule: the device-count flag is never set in the test process); each case
is its own test.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")

# (grid, origin, frequency, gather_mask); blocks 12x10x16, 8x6x10, 10x14x12
# and a ragged 9x7x9 (the pad-and-mask path, deviation (p) in DESIGN.md)
CASES = {
    "24x20x16": ((24, 20, 16), (0, 0, 0), 0.1, True),
    "16x12x10-origin": ((16, 12, 10), (5, 0, 9), 0.2, True),
    "20x28x12-origin": ((20, 28, 12), (100, 37, 3), 0.2, True),
    "17x13x9-ragged": ((17, 13, 9), (3, 11, 0), 0.25, False),
}

_WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import make_dpc_mesh
    from repro.core.distributed import _decomp_for
    from repro.data import perlin_noise
    from repro.topology import TopologyRequest, submit
    from oracles import oracle_components

    assert len(jax.devices()) == 4
    mesh = make_dpc_mesh((2, 2))
    out = {}
    for name, (grid, origin, freq, gather_mask) in json.loads(
            sys.argv[1]).items():
        field = perlin_noise(tuple(grid), freq, 0, tuple(origin))
        flat = np.sort(field.ravel())
        t = flat[int(0.9 * (flat.size - 1))]      # the top 10% lie above
        mask = field > t
        res = submit(TopologyRequest(
            "cc", backend="distributed", mesh=mesh, mask=jnp.asarray(mask),
            gather_mask=gather_mask, table_mode="replicated"))
        want = oracle_components(mask, 6)
        got = np.asarray(res.labels)
        # components that reach more than one block: the table resolves
        # real cuts
        dec = _decomp_for(mesh, tuple(grid))
        bx, by = np.meshgrid(np.arange(grid[0]) // dec.local[0],
                             np.arange(grid[1]) // dec.local[1],
                             indexing="ij")
        block = np.broadcast_to((bx * 2 + by)[..., None], mask.shape)
        crossing = sum(len(set(block[want == lab].tolist())) > 1
                       for lab in np.unique(want[want >= 0]))
        out[name] = {"equal": bool(np.array_equal(got, want)),
                     "masked": int(mask.sum()), "crossing": int(crossing),
                     "n_valid_slots": dec.n_valid_slots,
                     "stats": res.stats}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(_ROOT, "src"), os.path.dirname(__file__)]))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _WORKER, json.dumps(CASES)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(x for x in proc.stdout.splitlines()
                if x.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", list(CASES))
def test_weak_2x2_cc_matches_oracle(results, case):
    r = results[case]
    gather_mask = CASES[case][3]
    assert r["masked"] > 0 and r["crossing"] > 0, r
    assert r["equal"], f"labels differ from the oracle: {case}"
    st = r["stats"]
    assert st["table_iters"] >= 1
    assert st["converged"] == 1 and st["comm_phases"] == 1
    # in-domain slots of the gathered table: 4 bytes a label, one more a
    # slot where the mask is gathered too
    per_slot = 4 + (1 if gather_mask else 0)
    assert st["ghost_bytes"] == r["n_valid_slots"] * per_slot
