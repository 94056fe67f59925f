"""Unified topology query API — the facade over every CC / MS / manifold
entry point (DESIGN.md §Serve).

Callers describe WHAT they want in a `TopologyRequest` (query kind, domain,
backend, payload) instead of choosing among seven near-duplicate functions:

    query    "cc" | "ms" | "manifold" | "threshold_sweep"
    domain   "grid"  (structured, connectivity stencil)
           | "graph" (edge list: both directions of every undirected edge)
    backend  "pure"        (single device)
           | "distributed" (shard_map over a device mesh)

`submit(request)` routes one request to the legacy implementation —
bit-identical to calling it directly (the facade parity contract pinned by
`tests/test_topology_api.py`).  For batched multi-tenant serving with
layout bucketing and compiled-executable caching, hand the same requests to
`repro.serve.TopologyEngine` instead.

Routing table (query, domain, backend) -> legacy entry point:
    cc,  grid,  pure          core.connected_components.connected_components_grid
    cc,  graph, pure          core.connected_components.connected_components_graph
    cc,  grid,  distributed   core.distributed.distributed_connected_components
    cc,  graph, distributed   core.distributed_graph.distributed_connected_components_graph
    ms,  grid,  pure          core.ms_segmentation.ms_segmentation
    ms,  graph, pure          core.ms_segmentation.ms_segmentation_graph
    ms,  grid,  distributed   two core.distributed.distributed_manifold runs + the pair hash
    manifold, grid, pure      core.ms_segmentation.descending/ascending_manifold
    manifold, grid, distributed  core.distributed.distributed_manifold
    threshold_sweep, *, *     vmapped cc over `field > thresholds[k]`

Unsupported combinations raise NotImplementedError naming the gap (e.g.
manifold/ms on distributed graphs needs the order-field halo through
GraphDecomp's ghost layer — the ROADMAP carried item).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .core.connected_components import (connected_components_grid,
                                        connected_components_graph)
from .core.ms_segmentation import (ms_segmentation, ms_segmentation_graph,
                                   descending_manifold, ascending_manifold,
                                   _pair_hash)
from .core.distributed import (distributed_manifold,
                               distributed_connected_components,
                               distributed_connected_components_batch)
from .core.distributed_graph import (
    distributed_connected_components_graph,
    distributed_connected_components_graph_batch)
from .core._table import check_table_mode

QUERIES = ("cc", "ms", "manifold", "threshold_sweep")
DOMAINS = ("grid", "graph")
BACKENDS = ("pure", "distributed")


@dataclasses.dataclass(frozen=True)
class TopologyRequest:
    """One topology query.  Payload fields by query kind:

    cc               mask       (grid: bool array of any extent;
                                 graph: (n,) bool + senders/receivers)
    ms / manifold    order      (int order field — a total vertex order as
                                 produced by `core.compute_order`;
                                 `descending` picks the manifold direction)
    threshold_sweep  field + thresholds (labels CC of `field > t` per t)

    Distributed requests carry `mesh` (grid) or `mesh` + `decomp` (graph).
    `tag` is an opaque caller id, round-tripped onto the result.
    """
    query: str
    domain: str = "grid"
    backend: str = "pure"
    # payloads (query-dependent; unused fields stay None)
    mask: Any = None
    order: Any = None
    field: Any = None
    thresholds: Any = None
    senders: Any = None
    receivers: Any = None
    # knobs
    connectivity: int = 6
    descending: bool = True
    gather_mask: bool = True
    table_mode: str = "replicated"   # boundary/cut table layout: replicated
                                     # all_gather or sharded halo stack
                                     # (deviation (s) in DESIGN.md)
    table_max_iter: int = 64
    # distributed plumbing
    mesh: Any = None
    decomp: Any = None
    tag: Any = None

    def validate(self) -> None:
        if self.query not in QUERIES:
            raise ValueError(f"query {self.query!r} not in {QUERIES}")
        if self.domain not in DOMAINS:
            raise ValueError(f"domain {self.domain!r} not in {DOMAINS}")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        need = {"cc": ("mask",), "ms": ("order",), "manifold": ("order",),
                "threshold_sweep": ("field", "thresholds")}[self.query]
        for f in need:
            if getattr(self, f) is None:
                raise ValueError(f"{self.query} request needs {f}=")
        if self.domain == "graph" and (self.senders is None
                                       or self.receivers is None):
            raise ValueError("graph requests need senders= and receivers=")
        check_table_mode(self.table_mode)
        if self.table_mode != "replicated" and self.backend != "distributed":
            raise ValueError("table_mode='sharded' needs "
                             "backend='distributed' (the pure backends "
                             "have no boundary table)")
        if self.backend == "distributed":
            if self.mesh is None:
                raise ValueError("distributed requests need mesh=")
            if self.domain == "graph" and self.decomp is None:
                raise ValueError("distributed graph requests need decomp= "
                                 "(a core.GraphDecomp)")

    def shape(self):
        """Extent of the request's payload (the bucketing key input)."""
        for f in ("mask", "order", "field"):
            v = getattr(self, f)
            if v is not None:
                return tuple(v.shape)
        raise ValueError("request carries no payload")


@dataclasses.dataclass
class TopologyResult:
    """Facade result.  `labels` carries the query's label array (cc and
    manifold: one array shaped like the input; threshold_sweep: a leading
    (K,) thresholds dim); `ascending`/`descending`/`segmentation` are set
    for ms queries.  `stats` is the backend's DPCStats/GraphDPCStats as a
    uniform dict (distributed only); `meta` holds counters (rounds/iters).
    """
    query: str
    labels: Any = None
    ascending: Any = None
    descending: Any = None
    segmentation: Any = None
    stats: dict | None = None
    meta: dict = dataclasses.field(default_factory=dict)
    tag: Any = None


def _submit_cc(req: TopologyRequest) -> TopologyResult:
    if req.domain == "grid":
        if req.backend == "pure":
            res = connected_components_grid(req.mask, req.connectivity)
            return TopologyResult(
                "cc", labels=res.labels, tag=req.tag,
                meta={"n_rounds": res.n_rounds,
                      "n_compress_iter": res.n_compress_iter})
        labels, st = distributed_connected_components(
            req.mask, req.mesh, req.connectivity, req.gather_mask,
            table_mode=req.table_mode, table_max_iter=req.table_max_iter)
        return TopologyResult("cc", labels=labels, stats=st.as_dict(),
                              tag=req.tag)
    if req.backend == "pure":
        res = connected_components_graph(req.mask, req.senders,
                                         req.receivers)
        return TopologyResult(
            "cc", labels=res.labels, tag=req.tag,
            meta={"n_rounds": res.n_rounds,
                  "n_compress_iter": res.n_compress_iter})
    labels, st = distributed_connected_components_graph(
        req.mask, req.decomp, req.mesh, req.gather_mask,
        table_mode=req.table_mode, table_max_iter=req.table_max_iter)
    return TopologyResult("cc", labels=labels, stats=st.as_dict(),
                          tag=req.tag)


def _submit_manifold(req: TopologyRequest) -> TopologyResult:
    if req.domain == "graph":
        raise NotImplementedError(
            "manifolds on distributed graphs need an order-field halo "
            "through GraphDecomp's ghost layer (ROADMAP carried item); "
            "for single-device graphs use query='ms'")
    if req.backend == "pure":
        fn = descending_manifold if req.descending else ascending_manifold
        labels, it = fn(req.order, req.connectivity)
        return TopologyResult("manifold",
                              labels=labels.reshape(req.order.shape),
                              meta={"n_iter": it}, tag=req.tag)
    labels, st = distributed_manifold(req.order, req.mesh, req.connectivity,
                                      req.descending,
                                      table_mode=req.table_mode,
                                      table_max_iter=req.table_max_iter)
    return TopologyResult("manifold", labels=labels, stats=st.as_dict(),
                          tag=req.tag)


def _submit_ms(req: TopologyRequest) -> TopologyResult:
    if req.domain == "graph":
        if req.backend == "distributed":
            raise NotImplementedError(
                "MS on distributed graphs needs the order-field halo "
                "(ROADMAP carried item)")
        res = ms_segmentation_graph(req.order, req.senders, req.receivers)
        return TopologyResult("ms", ascending=res.ascending,
                              descending=res.descending,
                              segmentation=res.segmentation,
                              meta={"n_iter_asc": res.n_iter_asc,
                                    "n_iter_desc": res.n_iter_desc},
                              tag=req.tag)
    if req.backend == "pure":
        res = ms_segmentation(req.order, req.connectivity)
        return TopologyResult("ms", ascending=res.ascending,
                              descending=res.descending,
                              segmentation=res.segmentation,
                              meta={"n_iter_asc": res.n_iter_asc,
                                    "n_iter_desc": res.n_iter_desc},
                              tag=req.tag)
    # distributed ms = both manifold directions + the (desc, asc) pair hash
    # (each direction bit-identical to the pure manifolds, so the hash is
    # bit-identical to pure ms_segmentation on the same order field)
    desc, st_d = distributed_manifold(req.order, req.mesh, req.connectivity,
                                      descending=True,
                                      table_mode=req.table_mode,
                                      table_max_iter=req.table_max_iter)
    asc, st_a = distributed_manifold(req.order, req.mesh, req.connectivity,
                                     descending=False,
                                     table_mode=req.table_mode,
                                     table_max_iter=req.table_max_iter)
    seg = _pair_hash(desc, asc, req.order.size)
    return TopologyResult("ms", ascending=asc, descending=desc,
                          segmentation=seg,
                          stats={"descending": st_d.as_dict(),
                                 "ascending": st_a.as_dict()},
                          tag=req.tag)


def _sweep_masks(req: TopologyRequest):
    thr = jnp.asarray(req.thresholds).reshape(-1)
    return thr, jnp.asarray(req.field)


def _submit_sweep(req: TopologyRequest) -> TopologyResult:
    """CC of `field > t` for every threshold t, vmapped over one field."""
    thr, field = _sweep_masks(req)
    if req.domain == "grid":
        if req.backend == "pure":
            labels = jax.vmap(
                lambda t: connected_components_grid(
                    field > t, req.connectivity).labels)(thr)
            return TopologyResult("threshold_sweep", labels=labels,
                                  tag=req.tag)
        labels, st = distributed_connected_components_batch(
            field[None] > thr.reshape((-1,) + (1,) * field.ndim),
            req.mesh, req.connectivity, req.gather_mask,
            table_mode=req.table_mode, table_max_iter=req.table_max_iter)
        return TopologyResult("threshold_sweep", labels=labels,
                              stats=st.as_dict(), tag=req.tag)
    if req.backend == "pure":
        labels = jax.vmap(
            lambda t: connected_components_graph(
                field > t, req.senders, req.receivers).labels)(thr)
        return TopologyResult("threshold_sweep", labels=labels, tag=req.tag)
    labels, st = distributed_connected_components_graph_batch(
        field[None] > thr[:, None], req.decomp, req.mesh, req.gather_mask,
        table_mode=req.table_mode, table_max_iter=req.table_max_iter)
    return TopologyResult("threshold_sweep", labels=labels,
                          stats=st.as_dict(), tag=req.tag)


_ROUTES = {"cc": _submit_cc, "ms": _submit_ms, "manifold": _submit_manifold,
           "threshold_sweep": _submit_sweep}


def submit(request: TopologyRequest) -> TopologyResult:
    """Route one request to its legacy implementation (bit-identical)."""
    with jax.profiler.TraceAnnotation("topology.submit"):
        request.validate()
        return _ROUTES[request.query](request)


def submit_many(requests) -> list:
    """Sequential reference path: one `submit` per request.  The batched
    engine (`repro.serve.TopologyEngine`) must match this bit-for-bit."""
    return [submit(r) for r in requests]


__all__ = ["TopologyRequest", "TopologyResult", "submit", "submit_many",
           "QUERIES", "DOMAINS", "BACKENDS"]
