"""`chip_smoke.py` on the CPU: its logic at a tiny size (the platform check
is bypassed through `run(require_tpu=False)`, a function argument, not a
program option), its checks against corrupted labels, and its refusal to
run — non-zero exit, no result line — without a TPU or without the repo."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402


def test_smoke_runs_at_tiny_size_on_cpu():
    result = chip_smoke.run(size=12, oracle_size=6, require_tpu=False)
    assert result == {"ok": True, "device": {"platform": "cpu",
                                             "kind": "cpu", "count": 1}}


def _tiny_inputs(size=10, seed=3):
    from repro.core import compute_order
    from repro.data.perlin import perlin_noise_device
    field = perlin_noise_device((size,) * 3, 0.1, seed)
    order = compute_order(field)
    mask = field > jnp.quantile(field, 0.7)
    return order, mask


def test_checks_catch_a_corrupted_label():
    """The parity and root/critical checks reject a single wrong label."""
    from repro.topology import submit
    order, mask = _tiny_inputs()
    ms = submit(chip_smoke.ms_request(order, "pure"))
    cc = submit(chip_smoke.cc_request(mask, "pure"))
    desc = ms.descending
    assert chip_smoke.check_manifold(desc, order, True) == []
    assert chip_smoke.check_cc(cc.labels, mask) == []

    flat = np.asarray(desc).ravel()
    v = int(np.flatnonzero(flat != np.arange(flat.size))[0])
    # point v at itself: it is not a maximum, so the label is no critical
    # vertex; point it at a non-root: the root check fails
    bad_self = jnp.asarray(flat.copy()).at[v].set(v).reshape(desc.shape)
    assert "critical" in chip_smoke.check_manifold(bad_self, order, True)
    assert not chip_smoke.same(bad_self, desc)
    bad_chain = jnp.asarray(flat.copy()).at[int(flat[v])].set(v)
    assert "root" in chip_smoke.check_manifold(
        bad_chain.reshape(desc.shape), order, True)

    lab = np.asarray(cc.labels).ravel()
    m = np.flatnonzero(lab >= 0)
    w = int(m[lab[m] != m][0])              # a masked non-root vertex
    bad_cc = jnp.asarray(lab.copy()).at[int(lab[w])].set(w)
    assert "root" in chip_smoke.check_cc(bad_cc.reshape(mask.shape), mask)
    assert not chip_smoke.same(bad_cc.reshape(mask.shape), cc.labels)
    unmasked = int(np.flatnonzero(lab < 0)[0])
    bad_mask = jnp.asarray(lab.copy()).at[unmasked].set(w)
    assert "unmasked" in chip_smoke.check_cc(bad_mask.reshape(mask.shape),
                                             mask)


def _run_script(cwd, script):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("alone", [False, True])
def test_script_refuses_without_tpu_or_repo(tmp_path, alone):
    """Run as the chip check runs it: from the checkout with JAX on the CPU,
    and from a directory holding only the script.  Both exit non-zero and
    print no result line."""
    if alone:
        shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    else:
        cwd = _ROOT
    proc = _run_script(cwd, "chip_smoke.py")
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
