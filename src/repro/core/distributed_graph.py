"""Distributed connected components on unstructured (edge-list) meshes.

The paper computes CC "in distributed structured and unstructured grids,
based either on the connectivity of the underlying mesh or a feature mask"
(paper §5); `distributed.py` covers the structured block lattice — this
module covers the unstructured side with the same phase structure, swapping
coordinate arithmetic for *table-driven* id maps:

  decomposition  GraphDecomp vertex-partitions a global edge list into
                 per-device local subgraphs plus a one-ring ghost layer
                 (the unstructured analog of BlockDecomp's ghost faces);
                 every global<->local id translation is a precomputed
                 lookup table instead of stride arithmetic.
  local phase    graph steepest-init (graph_mask_argmax with masked ghosts
                 pinned to self, Alg. 1 lines 6-8) + path compression +
                 the stitch fixpoint (Alg. 3, deviation (d) in DESIGN.md)
                 run entirely device-local — no collectives.
  ONE comm phase lax.all_gather of every partition's owned *cut* vertices
                 (owned vertices incident to an inter-partition edge) into
                 a replicated flat table; labels and the cut-vertex masks
                 ride the same gather (deviation (b) in DESIGN.md).
  resolution     pointer chase over the table (Alg. 2 lines 15-25, slot
                 lookup by sorted-gid search), then the hook+propagate
                 fixpoint over the static cut-edge list and equal-label
                 groups (deviation (d2) in DESIGN.md), then value-search
                 substitution — all shared with the block backend via
                 core/_table.py, executed identically on every device.

Ghost *input* values (the mask at ghost vertices) are materialised by the
input scatter `mask[local_gid]` rather than exchanged with ppermute — the
unstructured analog of the structured halo; see deviation (g1) in DESIGN.md.
Fixed SPMD shapes are obtained by padding: the ghost/edge/cut tables pad to
their maxima (deviation (g2) in DESIGN.md), and each partition's owned set
pads to `max(counts)` with inert sentinel slots (deviation (p)), so
*imbalanced* (METIS-style) partitions — and vertex counts that do not
divide the partition count — are first-class.

`GraphDPCStats.comm_phases` counts the bulk exchange phases actually traced
into the program (the paper's budget: exactly one for the replicated
table).  `table_mode="sharded"` (deviation (s) in DESIGN.md) replaces the
cut-table all_gather with a partition-adjacency halo: each device keeps its
own cut row plus one chunk per adjacent partition (`_GraphShardGeom`),
exchanged by a static schedule of `lax.ppermute` rounds, and resolves the
global components by the relayed max-flooding fixpoint of
`core/_table.sharded_fixpoint` — bit-identical labels, per-device table
bytes bounded by (1 + degree) cut rows instead of `nparts` rows.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ._shardmap import collective, shard_map_norep
from ._table import (check_converged, check_table_mode, pointer_chase,
                     make_group_max, hook_propagate, sharded_fixpoint,
                     value_substitute)
from .stats import GraphDPCStats
from .steepest import graph_mask_argmax
from .connected_components import _cc_fixpoint, _graph_stitch

_N_STATS = len(GraphDPCStats._fields)


class GraphDecomp:
    """Static geometry of a vertex partition of an edge-list mesh.

    The mirror of BlockDecomp for unstructured meshes: where BlockDecomp
    derives ghost faces and boundary-table slots from coordinate strides,
    GraphDecomp precomputes them as numpy lookup tables from the concrete
    edge list (senders/receivers carry BOTH directions of every undirected
    edge, the repo-wide graph convention).

    Partition: `part[v]` assigns vertex v to one of `nparts` devices;
    default is contiguous blocks of global ids (the leading blocks one
    larger when ``n % nparts != 0``).  ANY explicit assignment works —
    imbalanced counts, empty partitions, a future METIS partitioner: each
    partition's owned set is padded to ``n_owned = max(counts)`` with inert
    sentinel slots (deviation (p) in DESIGN.md), the same fixed-SPMD-shape
    mechanism the ghost/edge/cut tables already use (deviation (g2)).

    Per partition p:
      owned    the sorted global ids with part == p (padded to `n_owned`;
               pad entries carry gid `n`, dropped by the output scatter);
      ghosts   the one-ring: vertices of other partitions reached by a cut
               edge from p;
      local id index into sorted(owned ∪ ghosts), padded at the end to
               `n_local`.  Sorting by *global* id preserves the invariant
               the id-maximum arguments rely on (as the block backend's
               raveled blocks do implicitly): the local id order is exactly
               the global id order restricted to the local set, so local
               argmax/stitch maxima transfer verbatim to global ids;
      edges    every directed global edge with >= 1 endpoint owned by p,
               rewritten to local ids (padded with (0, 0) self-loops, which
               are no-ops for argmax and stitch);
      cut      owned vertices incident to an inter-partition edge; cut j of
               p owns slot ``p * c_max + j`` of the gathered table.

    Ids use int32 below 2**31 vertices and int64 above (requires
    `jax_enable_x64`, mirroring BlockDecomp's refusal to wrap silently).
    """

    def __init__(self, n_vertices, senders, receivers, nparts, part=None):
        self.n = int(n_vertices)
        self.nparts = int(nparts)
        if self.n < 1:
            raise ValueError("graph must have at least one vertex")
        if self.nparts < 1:
            raise ValueError("nparts must be >= 1")
        if self.n < 2**31:
            self.id_dtype = jnp.int32
        elif jax.config.jax_enable_x64:
            self.id_dtype = jnp.int64
        else:
            # without x64, jnp silently downcasts int64 -> int32 and global
            # ids past 2**31 would wrap negative; refuse instead
            raise ValueError(
                f"graph has {self.n} >= 2**31 vertices; the int64 id path "
                "requires jax_enable_x64")
        s = np.asarray(senders, dtype=np.int64).ravel()
        r = np.asarray(receivers, dtype=np.int64).ravel()
        if s.shape != r.shape:
            raise ValueError("senders and receivers must have equal length")
        if s.size and not (0 <= s.min() and s.max() < self.n
                           and 0 <= r.min() and r.max() < self.n):
            raise ValueError("edge endpoints out of range")
        if part is None:
            # contiguous blocks; when n is not divisible the leading
            # n % nparts blocks are one vertex larger (no rounding of the
            # requested size — raggedness is padded away below)
            sizes = [len(c) for c in
                     np.array_split(np.arange(self.n), self.nparts)]
            part = np.repeat(np.arange(self.nparts), sizes)
        part = np.asarray(part, dtype=np.int64).ravel()
        if part.shape[0] != self.n:
            raise ValueError("part must assign every vertex")
        if part.size and (part.min() < 0 or part.max() >= self.nparts):
            raise ValueError(f"part values must lie in [0, {self.nparts})")
        counts = np.bincount(part, minlength=self.nparts)
        # no balance requirement: every partition's owned set pads to the
        # maximum count with inert sentinel slots (deviation (p) in
        # DESIGN.md), so arbitrary METIS-style assignments are accepted
        self.part = part
        self.owned_counts = counts
        self.n_owned = int(counts.max())
        self.pad_fraction = 1.0 - self.n / (self.nparts * self.n_owned)

        ps, pr = part[s], part[r]
        cross = ps != pr
        owned, ghosts, cut = [], [], []
        for p in range(self.nparts):
            owned.append(np.flatnonzero(part == p))
            sel = (ps == p) & cross
            ghosts.append(np.unique(r[sel]))
            cut.append(np.unique(s[sel]))
        self.g_max = max((len(g) for g in ghosts), default=0)
        self.n_local = self.n_owned + self.g_max
        if self.n_local >= 2**31:
            raise ValueError("per-partition extent exceeds int32 local ids; "
                             "use more partitions")
        self.c_max = max((len(c) for c in cut), default=0)
        self.table_size = self.nparts * self.c_max
        self.n_cut = int(sum(len(c) for c in cut))  # real (non-pad) slots

        # owned set padded to n_owned; pad gids are the out-of-range `n`,
        # which the output scatter drops (deviation (p) in DESIGN.md)
        self.owned_gid = np.full((self.nparts, self.n_owned), self.n,
                                 np.int64)
        lgid = np.full((self.nparts, self.n_local), -1, np.int64)
        valid = np.zeros((self.nparts, self.n_local), bool)
        is_ghost = np.zeros((self.nparts, self.n_local), bool)
        owned_lidx = np.zeros((self.nparts, self.n_owned), np.int32)
        cut_lidx = np.full((self.nparts, self.c_max), -1, np.int32)
        slot_of = np.full(self.n, -1, np.int64)
        gid2lid = np.full(self.n, -1, np.int64)              # reused scratch
        eloc = []
        for p in range(self.nparts):
            o, g, c = owned[p], ghosts[p], cut[p]
            self.owned_gid[p, :len(o)] = o
            loc = np.sort(np.concatenate([o, g]))  # local order == gid order
            lgid[p, :len(loc)] = loc
            valid[p, :len(loc)] = True
            gid2lid[loc] = np.arange(len(loc))
            is_ghost[p, gid2lid[g]] = True
            owned_lidx[p, :len(o)] = gid2lid[o]
            if len(o) < self.n_owned:
                # pad owned slots point at the first invalid local slot
                # (len(o) < n_owned implies len(loc) < n_local): mask False
                # there, so the pad label is -1 everywhere downstream
                owned_lidx[p, len(o):] = min(len(loc), self.n_local - 1)
            cut_lidx[p, :len(c)] = gid2lid[c]
            slot_of[c] = p * self.c_max + np.arange(len(c))
            esel = (ps == p) | (pr == p)
            ls, lr = gid2lid[s[esel]], gid2lid[r[esel]]
            if ls.size and ((ls < 0).any() or (lr < 0).any()):
                # reachable when a cross-partition edge appears in only one
                # direction: the receiving side then lacks the ghost
                raise ValueError(
                    "edge list must contain BOTH directions of every "
                    "undirected edge (one-ring ghost closure violated)")
            eloc.append((ls, lr))
            gid2lid[loc] = -1
        self.e_max = max((len(ls) for ls, _ in eloc), default=0)
        self.edge_src = np.zeros((self.nparts, self.e_max), np.int32)
        self.edge_dst = np.zeros((self.nparts, self.e_max), np.int32)
        for p, (ls, lr) in enumerate(eloc):
            self.edge_src[p, :len(ls)] = ls
            self.edge_dst[p, :len(lr)] = lr
        self.local_gid, self.local_valid = lgid, valid
        self.local_ghost = is_ghost
        self.owned_lidx = owned_lidx
        self.cut_lidx = cut_lidx

        # cut edges in table-slot space (both directions already present)
        self.cut_edge_src = slot_of[s[cross]].astype(np.int32)
        self.cut_edge_dst = slot_of[r[cross]].astype(np.int32)
        # sorted gid -> slot lookup for the pointer chase (the table-driven
        # stand-in for BlockDecomp.boundary_pos)
        allcut = np.concatenate(cut)
        order = np.argsort(allcut)
        self.cut_gid_sorted = allcut[order]
        self.cut_slot_sorted = slot_of[allcut[order]].astype(np.int32)


class _GraphShardGeom:
    """Sharded-table geometry of a vertex partition (deviation (s)).

    The unstructured analog of the block backend's `_ShardGeom`: where the
    lattice derives neighbor chunks from the mesh axes, here the *partition
    adjacency graph* (two partitions are adjacent iff a cut edge joins
    them) is read off the concrete cut-edge list.  Every partition's stack
    holds its own cut row (chunk 0) plus one chunk per adjacent partition,
    padded to the global maximum degree `d_max` with inert fill chunks.

    The halo exchange is a static schedule of `lax.ppermute` rounds: the
    directed receive pairs {(q -> p) : q adjacent to p} are greedily
    decomposed into partial permutations (ppermute forbids duplicate
    sources, so a partition multicasting its row to `deg` neighbors spans
    >= deg rounds; bipartite edge coloring bounds the schedule at d_max
    rounds, the greedy pass may use slightly more).  `store_idx[p, k]` says
    which chunk partition p stores round k's received row into — `n_chunks`
    (out of range, dropped) when p receives nothing that round.  All of
    this is numpy precomputed once per decomposition and threaded into the
    shard_map as per-device rows, like the other GraphDecomp tables.
    """

    def __init__(self, dec: GraphDecomp):
        c = dec.c_max
        pe_s = dec.cut_edge_src // max(c, 1)
        pe_d = dec.cut_edge_dst // max(c, 1)
        adjset = [set() for _ in range(dec.nparts)]
        for a, b in zip(pe_s.tolist(), pe_d.tolist()):
            adjset[a].add(b)
            adjset[b].add(a)
        adj = [sorted(s) for s in adjset]
        self.d_max = max((len(a) for a in adj), default=0)
        self.n_chunks = 1 + self.d_max
        self.stack_size = self.n_chunks * c
        chunk_of = np.full((dec.nparts, dec.nparts), -1, np.int32)
        for p in range(dec.nparts):
            chunk_of[p, p] = 0
            for i, q in enumerate(adj[p]):
                chunk_of[p, q] = 1 + i
        self.chunk_of = chunk_of

        pairs = [(q, p) for p in range(dec.nparts) for q in adj[p]]
        perms = []
        while pairs:
            used_s, used_d, rnd, rest = set(), set(), [], []
            for q, p in pairs:
                if q not in used_s and p not in used_d:
                    used_s.add(q)
                    used_d.add(p)
                    rnd.append((q, p))
                else:
                    rest.append((q, p))
            perms.append(tuple(rnd))
            pairs = rest
        self.round_perms = tuple(perms)
        store_idx = np.full((dec.nparts, max(len(perms), 1)), self.n_chunks,
                            np.int32)
        for k, rnd in enumerate(perms):
            for q, p in rnd:
                store_idx[p, k] = chunk_of[p, q]
        self.store_idx = store_idx

        # cut edges rewritten to per-partition stack slots: edge (u -> v)
        # appears in p's list iff BOTH endpoint partitions have a chunk in
        # p's stack; pad rows with src == stack_size (gated + dropped)
        srow = dec.cut_edge_src % max(c, 1)
        drow = dec.cut_edge_dst % max(c, 1)
        lists = []
        for p in range(dec.nparts):
            cs, cd = chunk_of[p, pe_s], chunk_of[p, pe_d]
            sel = (cs >= 0) & (cd >= 0)
            lists.append((cs[sel] * c + srow[sel], cd[sel] * c + drow[sel]))
        self.se_max = max((len(a) for a, _ in lists), default=0)
        ses = np.full((dec.nparts, max(self.se_max, 1)), self.stack_size,
                      np.int32)
        sed = np.zeros((dec.nparts, max(self.se_max, 1)), np.int32)
        for p, (a, b) in enumerate(lists):
            ses[p, :len(a)] = a
            sed[p, :len(b)] = b
        self.stack_edge_src = ses
        self.stack_edge_dst = sed


def _graph_shard_geom(dec: GraphDecomp) -> _GraphShardGeom:
    """The sharded geometry, built once per decomposition (numpy)."""
    geom = dec.__dict__.get("_shard_geom")
    if geom is None:
        geom = dec.__dict__["_shard_geom"] = _GraphShardGeom(dec)
    return geom


def _slot_lookup(dec: GraphDecomp):
    """(values -> (hit, slot)) via the sorted cut-gid table."""
    sg = jnp.asarray(dec.cut_gid_sorted, dtype=dec.id_dtype)
    sl = jnp.asarray(dec.cut_slot_sorted)

    def lookup(v):
        i = jnp.clip(jnp.searchsorted(sg, jnp.clip(v, 0)), 0, sg.size - 1)
        hit = (v >= 0) & (sg[i] == jnp.clip(v, 0))
        return hit, sl[i]

    return lookup


def _cc_partition(local_mask, lgid, local_ghost, owned_lidx, es, er,
                  cut_lidx, *shard, dec: GraphDecomp, name: str,
                  gather_mask: bool, table_mode: str = "replicated",
                  table_max_iter: int = 64):
    """One partition's program (runs under shard_map; leading axis is the
    singleton shard dim).  `shard` carries the sharded-geometry rows
    (store_idx, chunk_of, stack edges) when table_mode == "sharded"."""
    m = local_mask[0]
    gid = lgid[0]
    ghost = local_ghost[0]
    ol = owned_lidx[0]
    s, r = es[0], er[0]
    cl = cut_lidx[0]
    dt = dec.id_dtype

    # 1.+2. init: largest masked neighbor id; masked ghosts pretend self
    d0 = graph_mask_argmax(m, s, r, ghost=ghost)

    # 3. local CC fixpoint (stitch + compress, Alg. 3) in local ids
    res = _cc_fixpoint(d0, lambda d: _graph_stitch(d, m, s, r, dec.n_local))

    # 4. to global ids
    dg = jnp.where(res.labels >= 0, gid[jnp.clip(res.labels, 0)], dt(-1))
    owned = dg[ol]

    isz = jnp.dtype(dt).itemsize
    if dec.table_size == 0:
        # no inter-partition edges (or a single partition): fully local
        final = owned
        table_iters = jnp.int32(0)
        ghost_bytes = jnp.float32(0.0)
        masked_frac = jnp.float32(0.0)
        comm = jnp.int32(0)
        exch_rounds = jnp.int32(0)
        table_bytes = jnp.float32(0.0)
        converged = jnp.int32(1)
    elif table_mode == "replicated":
        # 5. the ONE communication phase: owned cut labels (+ masks in the
        #    same gather; gather_mask=False derives M = T >= 0 instead,
        #    DESIGN.md §Perf)
        cvalid = cl >= 0
        cli = jnp.clip(cl, 0)
        cut_lab = jnp.where(cvalid, dg[cli], dt(-1))
        if gather_mask:
            cut_m = jnp.where(cvalid, m[cli], False)
            payload = jnp.stack([cut_lab, cut_m.astype(dt)])
        else:
            payload = cut_lab[None]
        g = collective(lax.all_gather, payload, name)  # (nparts, rows, c_max)
        T = g[:, 0, :].reshape(-1)
        M = (g[:, 1, :].reshape(-1) != 0) if gather_mask else (T >= 0)

        # 6a. positional chase (Alg. 2 lines 15-25, table-driven lookup)
        slot_lookup = _slot_lookup(dec)

        def chase_lookup(t):
            hit, slot = slot_lookup(t)
            return jnp.where(hit, t[jnp.clip(slot, 0, t.size - 1)], t)

        Tstar, chase_iters, chase_ok = pointer_chase(T, chase_lookup,
                                                     table_max_iter)

        # 6b. hook + propagate over the static cut-edge list (deviation (d2))
        group_max, perm, sorted_vals = make_group_max(Tstar)
        ces = jnp.asarray(dec.cut_edge_src)
        ced = jnp.asarray(dec.cut_edge_dst)

        def cut_max(L):
            ok = M[ces] & M[ced]
            tgt = jnp.where(ok, ces, L.size)
            return L.at[tgt].max(jnp.where(ok, L[ced], dt(-1)), mode="drop")

        G, prop_iters, prop_ok = hook_propagate(Tstar, cut_max, group_max,
                                                table_max_iter)

        # 7. substitution: chase own label once, adopt its group's maximum
        hit, slot = slot_lookup(owned)
        chased = jnp.where(hit, Tstar[jnp.clip(slot, 0, Tstar.size - 1)],
                           owned)
        final = value_substitute(owned, chased, sorted_vals, G[perm])
        table_iters = chase_iters + prop_iters
        rows = 2 if gather_mask else 1
        # pad cut slots (cut_lidx == -1) carry label -1 / mask False and are
        # excluded from the exchange accounting (deviation (p) in DESIGN.md)
        ghost_bytes = jnp.float32(dec.n_cut * rows * isz)
        masked_frac = (jnp.sum(M).astype(jnp.float32)
                       / jnp.float32(max(dec.n_cut, 1)))
        comm = jnp.int32(1)
        exch_rounds = jnp.int32(0)
        # gathered payload (labels + mask as id dtype), or labels + bool M
        table_bytes = jnp.float32(
            dec.table_size * ((2 * isz) if gather_mask else (isz + 1)))
        converged = (chase_ok & prop_ok).astype(jnp.int32)
    else:
        # 5'-7'. sharded (deviation (s)): own cut row + one chunk per
        #    adjacent partition, max-flooding relayed by ppermute rounds —
        #    no all_gather.  The flood relation (masked in-stack cut edges +
        #    equal-static-label groups within the stack) connects exactly
        #    each global component's slots; its unique monotone fixpoint is
        #    the component max, the value the replicated chase+propagate
        #    computes (DESIGN.md §Table-sharding).
        geom = _graph_shard_geom(dec)
        store, chunk_row, ses, sed = (a[0] for a in shard)
        size = geom.stack_size
        cvalid = cl >= 0
        cli = jnp.clip(cl, 0)
        cut_lab = jnp.where(cvalid, dg[cli], dt(-1))

        def make_exchange(fill):
            def exchange(own_row):
                stack = jnp.full((geom.n_chunks, dec.c_max), fill,
                                 own_row.dtype)
                stack = stack.at[0].set(own_row)
                for k, perm_k in enumerate(geom.round_perms):
                    recv = collective(lax.ppermute, own_row, name, perm_k)
                    stack = stack.at[store[k]].set(recv, mode="drop")
                return stack.reshape(-1)
            return exchange

        # static stacks, exchanged once: the group structure and the mask
        exchange = make_exchange(-1)
        T0s = exchange(cut_lab)
        if gather_mask:
            cut_m = jnp.where(cvalid, m[cli], False)
            Ms = make_exchange(False)(cut_m)
        else:
            Ms = T0s >= 0            # labels are -1 iff unmasked
        group_max, perm, sorted_vals = make_group_max(T0s)

        def cut_max(L):
            ss = jnp.clip(ses, 0, size - 1)
            dd = jnp.clip(sed, 0, size - 1)
            ok = (ses < size) & Ms[ss] & Ms[dd]
            tgt = jnp.where(ok, ss, size)
            return L.at[tgt].max(jnp.where(ok, L[dd], dt(-1)), mode="drop")

        def refine(stack):
            return hook_propagate(stack, cut_max, group_max, table_max_iter)

        def reduce_any(x):
            return collective(lax.pmax, x.astype(jnp.int32), name) > 0

        stackG, _, rounds, iters, ok = sharded_fixpoint(
            cut_lab, exchange, refine, reduce_any,
            max_rounds=table_max_iter)

        # substitution: an owned label is a local vertex id, so its slot
        # (when it is a cut vertex) lives in this stack — own chunk or an
        # adjacent partition's; interior roots are found by value over the
        # static stack labels, exactly as in the replicated value search
        slot_lookup = _slot_lookup(dec)
        hit, slot = slot_lookup(owned)
        chunk = chunk_row[jnp.clip(slot // dec.c_max, 0, dec.nparts - 1)]
        sidx = chunk * dec.c_max + slot % dec.c_max
        chased = jnp.where(hit & (chunk >= 0),
                           stackG[jnp.clip(sidx, 0, size - 1)], owned)
        final = value_substitute(owned, chased, sorted_vals, stackG[perm])

        table_iters = collective(lax.pmax, iters, name)
        exch_rounds = rounds
        comm = rounds + jnp.int32(1)     # +1: the static label/mask stacks
        halo = size - dec.c_max
        ghost_bytes = (jnp.float32(halo * isz)
                       * (rounds.astype(jnp.float32) + 1.0)
                       + (jnp.float32(halo) if gather_mask else 0.0))
        # evolving stack + static label stack + own row + bool mask stack
        table_bytes = jnp.float32((2 * size + dec.c_max) * isz + size)
        # global fraction over real slots (== the replicated number: pad
        # slots are mask-False on both paths, deviation (p))
        masked_frac = (collective(
            lax.psum, jnp.sum(Ms[:dec.c_max]).astype(jnp.float32), name)
            / jnp.float32(max(dec.n_cut, 1)))
        converged = collective(lax.pmin, ok.astype(jnp.int32), name)

    stats = GraphDPCStats(
        local_iters=collective(lax.pmax, res.n_compress_iter, name),
        table_iters=table_iters,
        stitch_rounds=collective(lax.pmax, res.n_rounds, name),
        ghost_bytes=ghost_bytes,
        masked_ghost_fraction=masked_frac,
        comm_phases=comm,
        pad_fraction=jnp.float32(dec.pad_fraction),
        table_bytes_peak=table_bytes,
        exchange_rounds=exch_rounds,
        converged=converged,
    )
    return final[None], stats


def _shard_geom_args(decomp: GraphDecomp, table_mode: str):
    """The per-device sharded-geometry rows threaded into the shard_map
    (empty for the replicated layout)."""
    if table_mode != "sharded" or decomp.table_size == 0:
        return ()
    geom = _graph_shard_geom(decomp)
    return (jnp.asarray(geom.store_idx), jnp.asarray(geom.chunk_of),
            jnp.asarray(geom.stack_edge_src),
            jnp.asarray(geom.stack_edge_dst))


def distributed_connected_components_graph(mask, decomp: GraphDecomp,
                                           mesh: Mesh,
                                           gather_mask: bool = True,
                                           table_mode: str = "replicated",
                                           table_max_iter: int = 64):
    """Mask-implicit connected components of a vertex-partitioned edge-list
    mesh (Alg. 3 + Alg. 2 on a table-driven decomposition).

    mask: global (n,) bool array (the feature mask; all-ones labels pure
    geometry).  mesh: 1-D device mesh with `decomp.nparts` devices (e.g.
    ``make_dpc_mesh(nparts)``).  table_mode picks the cut-table layout —
    "replicated" (one all_gather) or "sharded" (own cut row + one chunk per
    adjacent partition, ppermute exchange rounds; deviation (s) in
    DESIGN.md).  Returns (labels, GraphDPCStats): labels is the global (n,)
    array carrying the largest vertex id of each component, -1 where
    unmasked — bit-identical to single-device `connected_components_graph`
    under every table_mode.
    """
    check_table_mode(table_mode)
    names = tuple(mesh.axis_names)
    if len(names) != 1:
        raise ValueError(f"graph CC needs a 1-D mesh, got axes {names}")
    name = names[0]
    if int(mesh.shape[name]) != decomp.nparts:
        raise ValueError(f"mesh has {mesh.shape[name]} devices but decomp "
                         f"has {decomp.nparts} partitions")
    dt = decomp.id_dtype
    mask = mask.ravel().astype(bool)
    if mask.shape[0] != decomp.n:
        raise ValueError(f"mask has {mask.shape[0]} entries for "
                         f"{decomp.n} vertices")

    lgid = jnp.asarray(decomp.local_gid, dtype=dt)
    valid = jnp.asarray(decomp.local_valid)
    # ghost input values ride the input scatter (deviation (g1) in
    # DESIGN.md): every partition reads its owned + one-ring mask here
    local_mask = jnp.where(valid, mask[jnp.clip(lgid, 0)], False)
    geom_args = _shard_geom_args(decomp, table_mode)

    fn = partial(_cc_partition, dec=decomp, name=name,
                 gather_mask=gather_mask, table_mode=table_mode,
                 table_max_iter=table_max_iter)
    spec = P(name, None)
    mapped = shard_map_norep(fn, mesh, (spec,) * (7 + len(geom_args)),
                             (spec, GraphDPCStats(*([P()] * _N_STATS))))
    owned_stack, stats = mapped(
        local_mask, lgid, jnp.asarray(decomp.local_ghost),
        jnp.asarray(decomp.owned_lidx),
        jnp.asarray(decomp.edge_src), jnp.asarray(decomp.edge_dst),
        jnp.asarray(decomp.cut_lidx), *geom_args)
    check_converged(stats.converged, "distributed_connected_components_graph",
                    table_max_iter)

    # unpermute the (nparts, n_owned) owned labels back to global id order;
    # pad slots carry gid n and fall off the scatter (deviation (p))
    labels = jnp.zeros(decomp.n, dtype=dt).at[
        jnp.asarray(decomp.owned_gid.reshape(-1))].set(
        owned_stack.reshape(-1), mode="drop")
    return labels, stats


def distributed_connected_components_graph_batch(masks, decomp: GraphDecomp,
                                                 mesh: Mesh,
                                                 gather_mask: bool = True,
                                                 table_mode: str =
                                                 "replicated",
                                                 table_max_iter: int = 64):
    """Batched `distributed_connected_components_graph`: masks is a (B, n)
    stack of feature masks over ONE decomposed mesh (the multi-tenant
    serving case: many masks / thresholds of the same geometry).  The
    per-partition program is vmapped inside one shard_map, so the single
    cut-table all_gather fires once for the whole batch (DESIGN.md §Serve).
    Returns ((B, n) labels, GraphDPCStats with a leading (B,) dim); per item
    bit-identical to the single-request call.
    """
    check_table_mode(table_mode)
    names = tuple(mesh.axis_names)
    if len(names) != 1:
        raise ValueError(f"graph CC needs a 1-D mesh, got axes {names}")
    name = names[0]
    if int(mesh.shape[name]) != decomp.nparts:
        raise ValueError(f"mesh has {mesh.shape[name]} devices but decomp "
                         f"has {decomp.nparts} partitions")
    dt = decomp.id_dtype
    masks = masks.reshape(masks.shape[0], -1).astype(bool)
    if masks.shape[1] != decomp.n:
        raise ValueError(f"masks have {masks.shape[1]} entries for "
                         f"{decomp.n} vertices")
    B = masks.shape[0]

    lgid = jnp.asarray(decomp.local_gid, dtype=dt)
    valid = jnp.asarray(decomp.local_valid)
    # (nparts, B, n_local): the ghost-input scatter (deviation (g1)) for
    # every request at once
    local_mask = jnp.where(valid[:, None, :],
                           masks[:, jnp.clip(lgid, 0)].transpose(1, 0, 2),
                           False)
    geom_args = _shard_geom_args(decomp, table_mode)

    part_fn = partial(_cc_partition, dec=decomp, name=name,
                      gather_mask=gather_mask, table_mode=table_mode,
                      table_max_iter=table_max_iter)

    def fn(local_mask, lgid, ghost, ol, es, er, cl, *geom):
        # local_mask: (1, B, n_local); the rest carry the singleton shard dim
        def one(m):
            return part_fn(m[None], lgid, ghost, ol, es, er, cl, *geom)
        owned, stats = jax.vmap(one)(local_mask[0])   # owned: (B, 1, n_owned)
        return owned.transpose(1, 0, 2), stats

    spec = P(name, None)
    bspec = P(name, None, None)
    mapped = shard_map_norep(
        fn, mesh, (bspec,) + (spec,) * (6 + len(geom_args)),
        (bspec, GraphDPCStats(*([P(None)] * _N_STATS))))
    owned_stack, stats = mapped(
        local_mask, lgid, jnp.asarray(decomp.local_ghost),
        jnp.asarray(decomp.owned_lidx),
        jnp.asarray(decomp.edge_src), jnp.asarray(decomp.edge_dst),
        jnp.asarray(decomp.cut_lidx), *geom_args)
    check_converged(stats.converged,
                    "distributed_connected_components_graph_batch",
                    table_max_iter)

    labels = jnp.zeros((B, decomp.n), dtype=dt).at[
        :, jnp.asarray(decomp.owned_gid.reshape(-1))].set(
        owned_stack.transpose(1, 0, 2).reshape(B, -1), mode="drop")
    return labels, stats
