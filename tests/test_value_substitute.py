"""The value-search substitution (`_table.value_substitute`) against the
binary-search formula it replaced.

The oracle below is the left-sided `searchsorted` formula: it must agree bit
for bit with the sort-merge join on every case, including equal-value runs
whose propagated maxima differ (the run's leftmost slot wins), negative
labels, no and all matches, a one-slot table, the chunked and the
single-chunk path, a vmapped batch and int64 labels under x64.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import _table

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def searchsorted_oracle(o, chased, sorted_vals, g_sorted):
    idx = jnp.clip(jnp.searchsorted(sorted_vals, chased), 0,
                   sorted_vals.shape[0] - 1)
    found = sorted_vals[idx] == chased
    improved = jnp.where(found & (chased >= 0),
                         jnp.maximum(g_sorted[idx], chased), chased)
    return jnp.where(o < 0, -1, improved)


def _case(name, rng):
    """(o, chased, sorted_vals, g_sorted) as int32 numpy arrays."""
    if name == "duplicate_runs":
        # few distinct values, long runs, g differs inside a run
        sv = np.sort(rng.integers(0, 12, 64))
        g = rng.integers(0, 100, 64)
        chased = rng.integers(0, 14, 300)
        o = chased.copy()
    elif name == "negatives":
        sv = np.sort(np.concatenate([np.full(5, -1), rng.integers(-3, 40, 30)]))
        g = rng.integers(-1, 80, sv.size)
        chased = rng.integers(-3, 45, 300)
        o = np.where(rng.random(300) < 0.3, -1, chased)
        o[::17] = -5
    elif name == "no_matches":
        sv = np.sort(rng.integers(0, 50, 40)) * 2
        g = rng.integers(0, 200, 40)
        chased = rng.integers(0, 50, 300) * 2 + 1
        o = chased.copy()
    elif name == "all_match":
        sv = np.sort(rng.integers(0, 50, 40))
        g = rng.integers(0, 200, 40)
        chased = sv[rng.integers(0, 40, 300)]
        o = chased.copy()
    elif name == "one_slot":
        sv = np.array([7])
        g = np.array([31])
        chased = rng.integers(-1, 12, 300)
        o = chased.copy()
    elif name == "leftmost_wins":
        # the run of 5s has maxima 40, 70, 11 (sharded mode's fresh halo):
        # its leftmost slot's 40 is the answer
        sv, g = [3, 5, 5, 5, 9], [3, 40, 70, 11, 9]
        chased, o = [5, 9, 4, 5, -1, 3], [1, 1, 1, 1, -1, -1]
    elif name == "extremes":
        # labels at both ends of int32, with and without a slot of their own
        top, bottom = 2**31 - 1, -2**31
        sv = [bottom, -1, 0, 5, top - 1]
        g = [bottom, -1, top, 6, top - 1]
        chased = rng.choice([bottom, -1, 0, 5, 7, top - 1, top], 300)
        o = np.where(rng.random(300) < 0.2, -1, np.abs(chased))
    elif name == "wrapping_results":
        # per-run results far apart: their differences overflow int32
        sv = np.sort(rng.integers(0, 2**30, 64))
        g = np.where(rng.random(64) < 0.5, 2**31 - 1 - rng.integers(0, 9, 64),
                     sv)
        chased = np.where(rng.random(300) < 0.7, sv[rng.integers(0, 64, 300)],
                          rng.integers(0, 2**31 - 1, 300))
        o = chased.copy()
    else:
        raise ValueError(name)
    return tuple(np.asarray(a, np.int32) for a in (o, chased, sv, g))


CASES = ["duplicate_runs", "leftmost_wins", "negatives", "no_matches",
         "all_match", "one_slot", "extremes", "wrapping_results"]


@pytest.mark.parametrize("chunk", [1 << 23, 64, 7], ids=["single_chunk",
                                                         "chunked",
                                                         "table_wide"])
@pytest.mark.parametrize("case", CASES)
def test_join_matches_searchsorted(case, chunk, monkeypatch):
    """Bit parity with the binary search: one join for the whole block, or
    static chunks whose last one is clamped back over its predecessor
    ("table_wide": the chunk widens to the table's size)."""
    monkeypatch.setattr(_table, "_JOIN_CHUNK", chunk)
    args = _case(case, np.random.default_rng(CASES.index(case)))
    got = jax.jit(_table.value_substitute)(*args)
    want = searchsorted_oracle(*args)
    if case == "leftmost_wins":
        np.testing.assert_array_equal(np.asarray(want), [40, 9, 4, 40, -1, -1])
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("chunk", [1 << 23, 50], ids=["single_chunk",
                                                     "chunked"])
def test_join_under_vmap(chunk, monkeypatch):
    """The batched entry points vmap the substitution over requests."""
    monkeypatch.setattr(_table, "_JOIN_CHUNK", chunk)
    rng = np.random.default_rng(5)
    cases = [_case(c, rng) for c in ("duplicate_runs", "negatives")]
    o = np.stack([c[0] for c in cases])
    chased = np.stack([c[1] for c in cases])
    m = min(c[2].size for c in cases)
    sv = np.stack([np.sort(c[2][:m]) for c in cases])
    g = np.stack([c[3][:m] for c in cases])
    got = jax.jit(jax.vmap(_table.value_substitute))(o, chased, sv, g)
    want = jax.vmap(searchsorted_oracle)(o, chased, sv, g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


_X64_WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_ENABLE_X64"] = "1"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import _table

    assert jax.config.jax_enable_x64
    rng = np.random.default_rng(11)
    base = 2**33
    sv = np.sort(np.concatenate([[-1, -1], base + rng.integers(0, 40, 60)]))
    g = np.where(sv < 0, -1, base + 2**31 + rng.integers(0, 2**20, sv.size))
    chased = np.where(rng.random(400) < 0.8,
                      sv[rng.integers(0, sv.size, 400)],
                      base + rng.integers(-5, 45, 400))
    o = np.where(rng.random(400) < 0.1, -1, chased)
    args = tuple(jnp.asarray(a, jnp.int64) for a in (o, chased, sv, g))

    def oracle(o, chased, sv, g):
        idx = jnp.clip(jnp.searchsorted(sv, chased), 0, sv.shape[0] - 1)
        found = sv[idx] == chased
        imp = jnp.where(found & (chased >= 0),
                        jnp.maximum(g[idx], chased), chased)
        return jnp.where(o < 0, -1, imp)

    want = np.asarray(oracle(*args))
    for chunk in (1 << 23, 64):
        _table._JOIN_CHUNK = chunk
        got = jax.jit(_table.value_substitute)(*args)
        assert got.dtype == jnp.int64, got.dtype
        assert (np.asarray(got) == want).all(), chunk
    assert want.max() > 2**33
    print("X64-JOIN-OK")
""")


def test_join_int64_labels_under_x64():
    """Subprocess: the x64 flag is global.  Labels past 2**33, results whose
    differences overflow int32."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", _X64_WORKER], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "X64-JOIN-OK" in proc.stdout
