"""`correct` comes out false when the timed path is broken underneath, and
the control (the reference one precision below, in the program's place)
fails the comparison.  Tiny cells on virtual CPU devices; the look for a
chip is skipped, the rest of a run is driven as on the chip."""
import json

import pytest

from conftest import run_cell, run_python

# the answer altered where it is produced: one label of every query
ALTER_ANSWER = """
import repro.topology as T
_submit = T.submit
def submit(req):
    res = _submit(req)
    for f in ("labels", "descending"):
        if getattr(res, f) is not None:
            setattr(res, f, getattr(res, f).at[(0,) * 3].add(1))
    return res
T.submit = submit
"""

# a step that returns its state unchanged: pointer doubling gives back
# the init pointers
STATE_UNCHANGED = """
import jax.numpy as jnp
import repro.core.distributed as D
D.path_compress = lambda d, max_iter=64: (d, jnp.int32(0))
"""

# the exchange between chips left out: halos and the boundary table see
# only this chip's own data
NO_EXCHANGE = """
import types
import jax.numpy as jnp
from jax import lax as L
import repro.core.distributed as D
fake = types.SimpleNamespace(**{k: getattr(L, k) for k in dir(L)
                                if not k.startswith("__")})
fake.ppermute = lambda x, axis_name, perm: x
fake.all_gather = lambda x, axis_name, **kw: jnp.broadcast_to(
    x, (L.psum(1, axis_name),) + x.shape)
D.lax = fake
"""


@pytest.mark.parametrize("workload", ["tiny1.ms_from_field",
                                      "tiny1.cc_top10",
                                      "tiny4.cc_top10"])
def test_sound_run_is_correct(tiny_root, workload):
    res, proc = run_cell(tiny_root, workload)
    assert res is not None, proc.stderr[-3000:]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())
    # the numbers compared are the last lines of standard error
    tail = proc.stderr.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("[check] ") for line in tail)


@pytest.mark.parametrize("workload,patch", [
    ("tiny1.ms_from_field", ALTER_ANSWER),
    ("tiny1.cc_top10", ALTER_ANSWER),
    ("tiny4.cc_top10", ALTER_ANSWER),
    ("tiny1.ms_from_field", STATE_UNCHANGED),
    ("tiny1.cc_top10", STATE_UNCHANGED),
    ("tiny4.cc_top10", STATE_UNCHANGED),
    ("tiny4.cc_top10", NO_EXCHANGE),
], ids=["ms-altered", "cc-altered", "cc4-altered", "ms-unchanged",
        "cc-unchanged", "cc4-unchanged", "cc4-no-exchange"])
def test_fault_is_not_correct(tiny_root, workload, patch):
    res, proc = run_cell(tiny_root, workload, patch=patch)
    assert res is not None, proc.stderr[-3000:]
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] > 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", ["tiny32.ms_from_field",
                                      "tiny32.cc_top10"])
def test_control_is_not_correct(tiny_root, workload):
    """The bfloat16 control fails a limit, while the program passes them
    all on every run (at 32^3, where the cc threshold's rounding band
    holds enough vertices)."""
    proc = run_python(tiny_root, f"""
        import control
        control.main(["--workload", {workload!r}, "--runs", "3"],
                     require_tpu=False)
    """, devices=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(x) for x in proc.stdout.strip().splitlines()
            if x.startswith("{")]
    assert len(rows) == 4
    assert any(v > 0 for v in rows[0]["control"].values())
    for row in rows[1:]:
        assert all(v == 0 for v in row["program"].values())


def test_no_chip_exits_nonzero_without_result(tiny_root):
    proc = run_python(tiny_root, """
        import run
        run.main()
    """.replace("run.main()", "sys.argv = ['run.py', '--workload', "
                "'tiny1.cc_top10', '--seed', '1', '--seconds', '1', "
                "'--trace', '0']; run.main()"), devices=1)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_without_the_program_exits_nonzero_without_result(tmp_path):
    """A directory that holds only BENCHMARK.json and bench/ has no system
    under test: the run fails before it prints a result."""
    import shutil
    from conftest import REPO
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = run_python(tmp_path, """
        import run
        run.run(["--workload", "perlin512.cc_top10", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], require_tpu=False)
    """, devices=1)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
