"""The plain reference agrees with the brute-force oracles of
`tests/oracles.py` (per-vertex loops) at small sizes."""
import sys

import numpy as np
import pytest

from conftest import REPO

sys.path.insert(0, str(REPO / "bench"))
sys.path.insert(0, str(REPO / "tests"))

import reference  # noqa: E402
from oracles import oracle_components, oracle_manifold  # noqa: E402

SHAPES = [(7, 5, 6), (11, 13, 3), (1, 9, 8), (16, 16, 16)]


def field_for(shape, seed, ties=False):
    rng = np.random.default_rng(seed)
    f = rng.standard_normal(shape).astype(np.float32)
    if ties:        # few distinct values, zeros of both signs among them
        f = np.round(f * 2) / 2
        f[f == 0] = np.where(rng.random(np.count_nonzero(f == 0)) < 0.5,
                             np.float32(-0.0), np.float32(0.0))
    return f


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("shape", SHAPES)
def test_order_field_is_the_lexsort_rank(shape, ties):
    f = field_for(shape, 1, ties)
    perm = np.lexsort((np.arange(f.size), f.ravel()))
    want = np.empty(f.size, np.int32)
    want[perm] = np.arange(f.size)
    got = reference.order_field(f)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got.ravel(), want)


def test_order_field_refuses_nan():
    f = np.zeros((2, 2, 2), np.float32)
    f[1, 1, 1] = np.nan
    with pytest.raises(ValueError):
        reference.order_field(f)


@pytest.mark.parametrize("descending", [True, False])
@pytest.mark.parametrize("shape", SHAPES)
def test_manifold_matches_oracle(shape, descending):
    order = reference.order_field(field_for(shape, 2, ties=True))
    want = oracle_manifold(order, 6, descending=descending)
    got = reference.manifold(order, descending)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("p", [0.1, 0.5, 0.9])
@pytest.mark.parametrize("shape", SHAPES)
def test_components_match_oracle(shape, p):
    mask = np.random.default_rng(3).random(shape) < p
    want = oracle_components(mask, 6)
    got = reference.components(mask)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def test_segmentation_wraps_like_int32():
    desc = np.array([[[5, 2**20]]], np.int32)
    asc = np.array([[[1, 3]]], np.int32)
    n = desc.size
    got = reference.segmentation(desc, asc)
    want = ((desc.astype(np.int64) * n + asc + 2**31) % 2**32
            - 2**31).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_mismatches_counts_vertices():
    a = np.arange(10, dtype=np.int32)
    b = a.copy()
    b[[2, 7]] = -1
    assert reference.mismatches(a, b) == 2
    assert reference.mismatches(a.astype(np.int64), b) == 10
