"""Plain reference for the benchmark's queries: numpy and scipy on the
host, independent of `src/repro`.

Semantics (the paper's §3-4, as the program documents them):

* order field: the rank of each vertex under the (value, flat index)
  lexsort of the float field (-0.0 ties 0.0);
* descending (ascending) manifold: the flat id of the maximum (minimum)
  that the steepest ascending (descending) path of each vertex reaches,
  over the 6-neighbourhood of the order field;
* segmentation: ``descending * n + ascending`` in int32 arithmetic (it
  wraps once n * n passes 2**31, as the program's hash does);
* connected components of a mask: the largest flat id of each vertex's
  6-connected masked component, -1 where unmasked.

The heavy passes run on host threads in x-slabs (numpy releases the
interpreter lock inside them).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy import ndimage

THREADS = max(1, min(16, os.cpu_count() or 1))


def _slabs(n0, parts=THREADS):
    edges = np.linspace(0, n0, min(parts, n0) + 1).astype(int)
    return [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:]) if b > a]


def _pmap(fn, items):
    with ThreadPoolExecutor(THREADS) as ex:
        return list(ex.map(fn, items))


def _check_conn(connectivity):
    if connectivity != 6:
        raise ValueError(f"reference supports connectivity 6, not "
                         f"{connectivity}")


def order_field(field: np.ndarray) -> np.ndarray:
    """int32 rank of every vertex under the (value, flat index) lexsort."""
    f = np.ascontiguousarray(field, dtype=np.float32).ravel()
    if np.isnan(f).any():
        raise ValueError("order_field: the field holds NaNs")
    n = f.size
    f = np.where(f == 0, np.float32(0), f)          # -0.0 ties 0.0
    bits = f.view(np.uint32)
    # monotone map float -> uint32, then the index in the low 32 bits: the
    # composite keys are unique, so one unstable sort gives the lexsort
    key = np.where(bits >> 31, ~bits, bits | np.uint32(1 << 31))
    comp = (key.astype(np.uint64) << np.uint64(32)) | np.arange(
        n, dtype=np.uint64)
    del key, bits, f
    comp.sort()
    perm = (comp & np.uint64(0xFFFFFFFF)).astype(np.int64)
    del comp
    order = np.empty(n, np.int32)
    order[perm] = np.arange(n, dtype=np.int32)
    return order.reshape(field.shape)


def _steepest(order: np.ndarray, descending: bool) -> np.ndarray:
    """Flat id of each vertex's steepest neighbour (itself at an
    extremum), over the 6-neighbourhood."""
    shape = order.shape
    strides = [int(np.prod(shape[a + 1:])) for a in range(3)]
    key = order if descending else -order
    out = np.empty(order.size, np.int32)

    def run(slab):
        x0, x1 = slab
        best = key[x0:x1].copy()
        delta = np.zeros(best.shape, np.int32)
        for a in range(3):
            for s in (-1, 1):
                src = [slice(x0, x1), slice(None), slice(None)]
                dst = [slice(None)] * 3
                lo, hi = (x0, x1) if a == 0 else (0, shape[a])
                # neighbour at +s along axis a, where it exists
                n_lo, n_hi = max(lo + s, 0), min(hi + s, shape[a])
                src[a] = slice(n_lo, n_hi)
                dst[a] = slice(n_lo - s - lo, n_hi - s - lo)
                nb = key[tuple(src)]
                cur = best[tuple(dst)]
                win = nb > cur
                cur[win] = nb[win]
                delta[tuple(dst)][win] = s * strides[a]
        ids = np.arange(x0 * strides[0], x1 * strides[0], dtype=np.int32)
        out[x0 * strides[0]:x1 * strides[0]] = ids + delta.ravel()

    _pmap(run, _slabs(shape[0]))
    return out


def _compress(d: np.ndarray) -> np.ndarray:
    """Follow every pointer to its root (pointer doubling to the
    fixpoint)."""
    chunks = _slabs(d.size, THREADS * 4)
    while True:
        nxt = np.empty_like(d)

        def jump(c):
            a, b = c
            np.take(d, d[a:b], out=nxt[a:b])
            return not np.array_equal(nxt[a:b], d[a:b])

        if not any(_pmap(jump, chunks)):
            return d
        d = nxt


def manifold(order: np.ndarray, descending: bool = True,
             connectivity: int = 6) -> np.ndarray:
    """Descending (ascending) manifold labels, shaped like `order`."""
    _check_conn(connectivity)
    if order.ndim != 3:
        raise ValueError("reference manifolds are for 3-D grids")
    return _compress(_steepest(order, descending)).reshape(order.shape)


def segmentation(desc: np.ndarray, asc: np.ndarray) -> np.ndarray:
    """desc * n + asc modulo 2**32, as int32."""
    n = np.uint32(desc.size % 2**32)
    u = desc.view(np.uint32) * n + asc.view(np.uint32)
    return u.view(np.int32)


def components(mask: np.ndarray, connectivity: int = 6) -> np.ndarray:
    """Largest flat id of each masked vertex's component, -1 elsewhere."""
    _check_conn(connectivity)
    n = mask.size
    # label the reversed grid: scipy numbers components in the order they
    # are first met, so in the reversed scan label j is the component whose
    # largest original flat id is the j-th met
    rev = np.ascontiguousarray(mask.ravel()[::-1].reshape(mask.shape))
    lab, k = ndimage.label(rev, ndimage.generate_binary_structure(
        mask.ndim, 1))
    flat = lab.ravel()
    del lab, rev
    seen = np.maximum.accumulate(flat)
    new = np.empty(n, bool)
    new[0] = flat[0] > 0
    np.greater(flat[1:], seen[:-1], out=new[1:])
    del seen
    first = np.flatnonzero(new)
    if first.size != k:
        raise AssertionError("scipy labels are not in first-met order")
    top = np.empty(k + 1, np.int32)
    top[0] = -1
    top[1:] = n - 1 - first
    return top[flat][::-1].reshape(mask.shape)


def mismatches(got, want) -> int:
    """Vertices at which two label arrays differ (a shape or dtype that
    differs counts every vertex)."""
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(want.size)
    return int(np.count_nonzero(got != want))
