"""The general query generator: one cell's inputs and its query, built from
the cell's configuration file (`bench/configs/<name>.json`: grid, layout,
table mode, field) and traffic file (`bench/traffic/<name>.json`: which
query, and its parameters).  A new mix of these query kinds is a new data
file; nothing here names a configuration or a mix.

Query kinds (the ``query`` key of a traffic file):

* ``ms``: ``compute_order(field)``, then Morse-Smale segmentation through
  ``repro.topology.submit`` (both manifolds and the segmentation);
* ``cc``: ``field > t`` with ``t`` the field's top-``top_fraction``
  threshold, fixed in set-up, then connected components.

Every query runs through ``backend="distributed"`` on the configuration's
mesh and ends in ``block_until_ready`` on all it returns.  Set-up warms
every program of the query on a field of the same shape and layout whose
answer takes almost no work (`warm_field`), so that it loads each program
without running the real query twice.
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import repro.topology as topology
from repro.core import compute_order, make_dpc_mesh

import fields
import reference

QUERIES = ("ms", "cc")
CONFIG_KEYS = ("grid", "layout", "origin", "frequency", "connectivity",
               "table_mode", "field_dtype")


def check_config(config):
    missing = [k for k in CONFIG_KEYS if k not in config]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    if config["field_dtype"] != "float32":
        raise ValueError("the field generator makes float32 fields only")


def check_traffic(traffic):
    kind = traffic.get("query")
    if kind not in QUERIES:
        raise ValueError(f"traffic query {kind!r} not in {QUERIES}")
    if kind == "cc" and not 0 < traffic.get("top_fraction", 0) < 1:
        raise ValueError("a cc mix needs 0 < top_fraction < 1")


# --- the field's order statistic (set-up), by bisection on sort keys -------


def _float_key(x):
    """int32 key whose signed order is the float order (-0.0 ties 0.0)."""
    x = jnp.where(x == 0, jnp.float32(0), x)
    b = lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))


def _key_to_float(k: int) -> np.float32:
    b = k ^ ((k >> 31) & 0x7FFFFFFF)
    return np.array([b], np.int32).view(np.float32)[0]


def _count_le(field, k):
    return jnp.sum(_float_key(field) <= k, dtype=jnp.int32)


_count_le_jit = jax.jit(_count_le)


def top_threshold(field, top_fraction: float) -> np.float32:
    """The value of rank floor((1 - top_fraction) (n - 1)), ascending: the
    vertices above it are the field's top `top_fraction`.  Exact, by
    bisection over the int32 keys (32 counting passes, no sort)."""

    n = field.size
    want = int((1.0 - top_fraction) * (n - 1)) + 1
    lo, hi = -2**31, 2**31 - 1
    while lo < hi:
        mid = (lo + hi) // 2
        if int(_count_le_jit(field, jnp.int32(mid))) >= want:
            hi = mid
        else:
            lo = mid + 1
    return _key_to_float(lo)


# --- fingerprints: every answer of the window against the reference --------


def _mix(x, ids):
    u = lax.bitcast_convert_type(x, jnp.uint32) if x.dtype == jnp.int32 \
        else x.astype(jnp.uint32)
    h = (u * jnp.uint32(0x9E3779B1)) ^ (ids * jnp.uint32(0x85EBCA77)
                                        + jnp.uint32(0x165667B1))
    return jnp.sum(h, dtype=jnp.uint32)


def _flat_ids(shape):
    ids = None
    for a in range(len(shape)):
        g = lax.broadcasted_iota(jnp.uint32, shape, a)
        ids = g if ids is None else ids * jnp.uint32(shape[a]) + g
    return ids


def _fingerprints(xs):
    return {k: _mix(v, _flat_ids(v.shape)) for k, v in xs.items()}


_fingerprints_jit = jax.jit(_fingerprints)


def device_fingerprint(arrays: dict):
    """uint32 per output, computed where the arrays live (no gather)."""
    return _fingerprints_jit(arrays)


def host_fingerprint(arrays: dict) -> dict:
    """`device_fingerprint` of host arrays, in numpy's wrapping uint32,
    summed in chunks on host threads."""
    from concurrent.futures import ThreadPoolExecutor

    def part(args):
        u, lo = args
        ids = np.arange(lo, lo + u.size, dtype=np.uint32)
        h = (u * np.uint32(0x9E3779B1)) ^ (ids * np.uint32(0x85EBCA77)
                                           + np.uint32(0x165667B1))
        return int(np.sum(h, dtype=np.uint32))

    out = {}
    for k, v in arrays.items():
        u = v.view(np.uint32).ravel() if v.dtype == np.int32 \
            else v.astype(np.uint32).ravel()
        step = -(-u.size // reference.THREADS)
        chunks = [(u[i:i + step], i) for i in range(0, u.size, step)]
        with ThreadPoolExecutor(reference.THREADS) as ex:
            out[k] = sum(ex.map(part, chunks)) % 2**32
    return out


# --- the cell ---------------------------------------------------------------


class Workload:
    """One cell: the configuration's field on its mesh, the query the
    traffic file names, and its plain reference."""

    def __init__(self, config, traffic, devices):
        check_config(config)
        check_traffic(traffic)
        self.config, self.traffic = config, traffic
        self.kind = traffic["query"]
        n = int(np.prod(config["layout"]))
        self.mesh = make_dpc_mesh(tuple(config["layout"]),
                                  devices=devices[:n])
        self.field = self.threshold = None
        self.trace_spans = False
        self.spans = {}

    def setup(self, log=print):
        self.field = jax.block_until_ready(
            fields.make_field(self.config, self.mesh))
        if self.kind == "cc":
            self.threshold = top_threshold(self.field,
                                           self.traffic["top_fraction"])
            masked = int(jnp.sum(self.field > self.threshold))
            log(f"[setup] threshold {float(self.threshold)!r}: "
                f"{masked} of {self.field.size} vertices masked "
                f"({masked / self.field.size:.6f})")

    def warm_field(self):
        """A field of the real one's shape, dtype and layout on which the
        query does almost no work, to load every program in set-up: for
        cc all -inf (an empty mask); for ms a checkerboard of 0 and 1, in
        which every vertex is an extremum or next to one (one doubling
        round per direction)."""
        shape = self.field.shape

        def make():
            if self.kind == "cc":
                return jnp.full(shape, -jnp.inf, jnp.float32)
            parity = sum(lax.broadcasted_iota(jnp.int32, shape, a)
                         for a in range(len(shape))) % 2
            return parity.astype(jnp.float32)

        return jax.jit(make, out_shardings=self.field.sharding)()

    @contextlib.contextmanager
    def _span(self, name):
        """Host span: a profiler annotation, and with `trace_spans` its
        seconds, ending in `block_until_ready` on what the body puts in
        the yielded list."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            box = []
            yield box
            if self.trace_spans and box:
                jax.block_until_ready(box)
        if self.trace_spans:
            self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def _request(self, query, **payload):
        return topology.TopologyRequest(
            query, backend="distributed", mesh=self.mesh,
            connectivity=self.config["connectivity"],
            table_mode=self.config["table_mode"], **payload)

    def query(self, field=None):
        """One query of `field` (the cell's own by default); returns
        (outputs: dict of device arrays, stats)."""
        field = self.field if field is None else field
        submit = topology.submit
        if self.kind == "cc":
            with self._span("mask"):
                mask = field > self.threshold
            with self._span("submit.cc"):
                res = submit(self._request("cc", mask=mask))
                out = {"labels": res.labels}
                jax.block_until_ready(out)
            return out, res.stats
        with self._span("order") as box:
            order = compute_order(field)
            box.append(order)
        with self._span("submit.ms"):
            res = submit(self._request("ms", order=order))
            out = {"order": order, "descending": res.descending,
                   "ascending": res.ascending,
                   "segmentation": res.segmentation}
            jax.block_until_ready(out)
        return out, res.stats

    def reference(self, field: np.ndarray, threshold=None) -> dict:
        """The plain reference's outputs for this cell, from the host copy
        of the field (no value the program made is used).  `threshold`
        replaces the cell's own (the control rounds both)."""
        conn = self.config["connectivity"]
        if self.kind == "cc":
            t = self.threshold if threshold is None else threshold
            return {"labels": reference.components(field > t, conn)}
        order = reference.order_field(field)
        desc = reference.manifold(order, True, conn)
        asc = reference.manifold(order, False, conn)
        return {"order": order, "descending": desc, "ascending": asc,
                "segmentation": reference.segmentation(desc, asc)}
