"""Id / order-field utilities shared by all DPC variants.

The paper (§3.1, §4.1) requires an injective scalar field, enforced by a
Simulation-of-Simplicity variant: globally sort vertices by (scalar, global
id) and use the sort rank as the *order field*.  All DPC code operates on
this integer order field, never on raw scalars.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _sort_key(flat: jax.Array) -> jax.Array:
    """int32 key whose signed order is the order `lax.sort` gives `flat`:
    zeros and NaNs canonicalised first (so -0.0 ties 0.0 and NaNs sort
    last), then the float's bits with the magnitude bits flipped for
    negatives.  Integer fields of 32 bits or fewer map directly."""
    if jnp.issubdtype(flat.dtype, jnp.floating):
        f = flat.astype(jnp.float32)
        f = jnp.where(f == 0, jnp.float32(0), f)
        f = jnp.where(jnp.isnan(f), jnp.float32(jnp.nan), f)
        b = lax.bitcast_convert_type(f, jnp.int32)
        return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    if flat.dtype == jnp.uint32:
        return lax.bitcast_convert_type(flat ^ jnp.uint32(1 << 31),
                                        jnp.int32)
    return flat.astype(jnp.int32)


def compute_order(scalars: jax.Array, ids: jax.Array | None = None) -> jax.Array:
    """Global order field: rank of each vertex under (scalar, id) lexsort.

    Mirrors TTK's ttkArrayPreconditioning (paper §4.1).  Returns int32 ranks
    in [0, N) — a permutation, hence injective.  The sort is one unstable
    int32 sort over (scalar key, id[, position]) — unique keys, so it is
    the stable lexsort's permutation bit for bit, and it compiles for a TPU
    several times faster than a stable float sort (DESIGN.md §Perf).
    float64 / 64-bit integer scalars (x64 only) keep the float lexsort.
    Under a caller's jit its ops carry the `dpc.order` scope; called
    eagerly they are dispatched op by op and carry none.  (One jitted
    program lowered the MS query's peak device memory on the chip: a change
    of its own, PERF.md §7.)
    """
    with jax.named_scope("dpc.order"):
        flat = scalars.ravel()
        n = flat.shape[0]
        pos = jnp.arange(n, dtype=jnp.int32)
        if flat.dtype.itemsize > 4:
            perm = jnp.lexsort((pos if ids is None else ids.ravel(), flat))
        else:
            keys = (_sort_key(flat),) + (() if ids is None
                                         else (ids.ravel(),))
            perm = lax.sort(keys + (pos,), num_keys=len(keys) + 1,
                            is_stable=False)[-1]
        order = jnp.zeros(n, dtype=jnp.int32).at[perm].set(
            pos, unique_indices=True)
        return order.reshape(scalars.shape)


def inverse_permutation(perm: jax.Array) -> jax.Array:
    """inv[perm[i]] = i.  Used to map max-order values back to vertex ids."""
    n = perm.shape[0]
    return jnp.zeros(n, dtype=perm.dtype).at[perm.ravel()].set(
        jnp.arange(n, dtype=perm.dtype)
    )


def flat_ids(shape, dtype=jnp.int32) -> jax.Array:
    """Row-major flat id grid for a structured grid of `shape`."""
    n = int(np.prod(shape))
    return jnp.arange(n, dtype=dtype).reshape(shape)


def compact_labels(labels: jax.Array, fill_value: int = -1):
    """Relabel arbitrary label values to [0, k).  Not jit-shape-stable in k;
    returns (compact, k).  Negative labels (unmasked) keep `fill_value`."""
    flat = labels.ravel()
    uniq = jnp.unique(flat, size=flat.shape[0], fill_value=jnp.iinfo(flat.dtype).max)
    idx = jnp.searchsorted(uniq, flat)
    neg = jnp.searchsorted(uniq, 0)  # number of negative labels
    compact = jnp.where(flat < 0, fill_value, idx - neg)
    k = int((uniq != jnp.iinfo(flat.dtype).max).sum() - int(neg))
    return compact.reshape(labels.shape), k
