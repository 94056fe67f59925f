"""Distributed Path Compression (paper Alg. 1 + Alg. 2) under shard_map.

Decomposition: N-D *blocks* over a multi-axis device mesh.  Mesh axis ``a``
decomposes grid axis ``a`` (a 1-D mesh recovers the original slab layout);
each block carries one layer of ghost vertices on every decomposed face —
the paper's "one layer of ghost vertices".  Ghost corners/edges are filled
by exchanging axis-by-axis on the progressively extended block, the standard
dimension-ordered halo exchange.

Grid extents need NOT divide the layout: blocks take the ceil-division
extent, the grid is padded up to ``layout * local`` per decomposed axis, and
padding is masked with sentinels that can never win an argmax or hook a
table row — order -1 (below every real order value) for manifolds, mask
False for CC, label -1 in the gathered boundary table (deviation (p) in
DESIGN.md).  `DPCStats.ghost_bytes`/`masked_ghost_fraction` count only
in-domain table slots; `pad_fraction` reports the padding overhead.

The local phase runs entirely in *local* extended-block ids.  Because every
vertex of the extended block has global coordinates ``origin + local``, the
local raveled order is exactly the global id order restricted to the block,
so id-maximum arguments (CC labels = largest member id) transfer verbatim;
local ids are converted to global flat ids by one gather through a
coordinate-arithmetic id map (replacing TTK's id-translation structures).

Phases (MS manifolds):
  1. halo exchange of the order field (one lax.ppermute pair per mesh axis);
  2. steepest init on the extended block; ghost vertices pretend to be
     maxima (point to themselves) — Alg. 1 lines 6-8;
  3. local path compression to the block fixpoint (no collectives);
  4. ONE global communication step: all_gather of every owned boundary
     *face* (two per decomposed axis) into a replicated flat table — the
     SPMD equivalent of Alg. 2's Gather->rank0->Scatter->Allgather staging
     (deviation (b) in DESIGN.md);
  5. pointer doubling on the gathered table — every device compresses the
     same table, resolving segments that stretch across multiple blocks
     (paper Fig. 2);
  6. final substitution: owned pointers that target any boundary vertex are
     replaced by the table's compressed target — Alg. 2 lines 27-33.

Connected components add the stitch pass locally (Alg. 3) and, on the
gathered table, a hook+propagate fixpoint over the static boundary
adjacency (all stencil edges between table vertices, which covers axis cuts
*and* diagonal block-to-block edges) and equal-label groups.  The paper
compresses the ghost table with path compression only; that is sufficient
for MS integral lines (strictly order-increasing chains) but not for CC
labels that must *merge* across a cut whose local roots are interior
vertices — deviation (d2) in DESIGN.md.  The fix stays within the paper's
single-communication-phase budget: it only post-processes the
already-gathered table.
"""
from __future__ import annotations

import math
from functools import cached_property, lru_cache, partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from ._shardmap import collective, shard_map_norep
from ._table import (TableView, chase_view, check_converged, check_table_mode,
                     make_group_max, hook_propagate, materialize,
                     sharded_fixpoint, value_substitute)
from .connected_components import grid_stitch
from .stats import DPCStats
from .steepest import neighbor_offsets
from .pathcompress import path_compress

AXIS = "shards"                 # legacy 1-D axis name (make_flat_mesh interop)
BLOCK_AXES = ("bx", "by", "bz")  # axis names used by make_dpc_mesh layouts

_N_STATS = len(DPCStats._fields)


def make_dpc_mesh(layout, devices=None) -> Mesh:
    """Device mesh for a block decomposition.

    layout: int (1-D slabs, legacy "shards" axis) or a tuple of up to three
    per-axis block counts, e.g. (4, 2) or (2, 2, 2); mesh axis ``a``
    decomposes grid axis ``a``.
    """
    if isinstance(layout, (int, np.integer)):
        layout, names = (int(layout),), (AXIS,)
    else:
        layout = tuple(int(p) for p in layout)
        if not 1 <= len(layout) <= len(BLOCK_AXES):
            raise ValueError(f"layout {layout} must have 1..3 axes")
        names = BLOCK_AXES[:len(layout)]
    # Auto axes: the labels that come back are ordinary sharded arrays that
    # any jnp op (a gather included) accepts without an out_sharding
    return jax.make_mesh(layout, names, (AxisType.Auto,) * len(layout),
                         devices=devices)


# --- static decomposition geometry ------------------------------------------


class BlockDecomp:
    """Static geometry of an N-D block decomposition of a structured grid.

    Grid axis ``a`` (a < k) is split into ``layout[a]`` ceil-division blocks
    mapped to mesh axis ``names[a]``; remaining grid axes stay whole.  When
    the extent does not divide, every block still gets the same static
    extent ``local[a] = ceil(grid[a] / layout[a])`` and the trailing cells
    (possibly whole trailing blocks) are padding, masked with sentinels that
    are inert in every phase — deviation (p) in DESIGN.md.  Provides the
    global<->local id arithmetic and the layout of the gathered boundary
    table: the table is the concatenation, over decomposed axes ``a``, of
    (nblocks, 2, face_size[a]) segments holding every block's lo/hi owned
    face along ``a`` (block order row-major in mesh-axis order, matching
    ``lax.all_gather(..., names)``).
    """

    def __init__(self, grid_shape, layout, names):
        self.grid = tuple(int(x) for x in grid_shape)
        self.layout = tuple(int(p) for p in layout)
        self.names = tuple(names)
        self.ndim = len(self.grid)
        self.k = len(self.layout)
        if self.k > self.ndim:
            raise ValueError(f"mesh has {self.k} axes but grid is "
                             f"{self.ndim}-D")
        self.local = tuple(
            -(-self.grid[i] // self.layout[i]) if i < self.k
            else self.grid[i]
            for i in range(self.ndim))
        # the statically padded grid the SPMD program actually runs on
        self.padded = tuple(
            self.local[i] * self.layout[i] if i < self.k else self.grid[i]
            for i in range(self.ndim))
        self.ragged = self.padded != self.grid
        self.ext = tuple(
            self.local[i] + 2 if i < self.k else self.local[i]
            for i in range(self.ndim))
        self.nblocks = math.prod(self.layout)
        self.size = math.prod(self.grid)
        if self.size < 2**31:
            self.id_dtype = jnp.int32
        elif jax.config.jax_enable_x64:
            self.id_dtype = jnp.int64
        else:
            # without x64, jnp silently downcasts int64 -> int32 and global
            # ids past 2**31 would wrap negative; refuse instead
            raise ValueError(
                f"grid has {self.size} >= 2**31 vertices; the int64 id path "
                "requires jax_enable_x64")
        # row-major strides of the global grid and of the block lattice
        self.stride = tuple(math.prod(self.grid[i + 1:])
                            for i in range(self.ndim))
        self.bstride = tuple(math.prod(self.layout[a + 1:])
                             for a in range(self.k))
        # per-axis owned-face geometry (face = local block minus that axis)
        self.face_stride, self.face_size, self.face_offset = [], [], []
        off = 0
        for a in range(self.k):
            st, acc = {}, 1
            for i in reversed([i for i in range(self.ndim) if i != a]):
                st[i] = acc
                acc *= self.local[i]
            self.face_stride.append(st)
            self.face_size.append(acc)
            self.face_offset.append(off)
            off += self.nblocks * 2 * acc
        self.table_size = off
        self.owned_slices = tuple(
            slice(1, self.local[i] + 1) if i < self.k else slice(None)
            for i in range(self.ndim))
        # closed-form count of in-domain table slots (pad slots excluded):
        # along axis a there are f_a valid lo/hi face positions, each
        # carrying prod(grid[i != a]) in-domain cells (the per-axis valid
        # cell counts sum back to the exact grid extent) — this is what
        # DPCStats.ghost_bytes reports (deviation (p) in DESIGN.md)
        self.n_valid_slots = 0
        for a in range(self.k):
            L = self.local[a]
            f = sum(int(b * L < self.grid[a]) + int(b * L + L - 1
                                                    < self.grid[a])
                    for b in range(self.layout[a]))
            self.n_valid_slots += f * (self.size // self.grid[a])
        self.pad_fraction = 1.0 - self.size / math.prod(self.padded)

    def boundary_pos(self, g, xp=jnp):
        """Map global flat ids to their canonical slot in the gathered
        boundary table.  Returns (is_boundary, flat_slot); a vertex on
        several faces (block edge/corner) is canonicalised to the lowest
        decomposed axis.  Works under numpy (static precompute) and jnp
        (traced lookups).  Only defined for in-domain ids — pad cells of a
        ragged decomposition never reach a lookup because their table
        entries carry the fixed sentinel -1 (deviation (p) in DESIGN.md)."""
        xs = [(g // self.stride[i]) % self.grid[i] for i in range(self.ndim)]
        B = 0
        for a in range(self.k):
            B = B + (xs[a] // self.local[a]) * self.bstride[a]
        is_b = xp.zeros_like(g, dtype=bool)
        pos = xp.zeros_like(g)
        for a in reversed(range(self.k)):
            L = self.local[a]
            xin = xs[a] % L
            on = (xin == 0) | (xin == L - 1)
            j = xp.where(xin == L - 1, 1, 0)
            r = 0
            for i in range(self.ndim):
                if i == a:
                    continue
                r = r + (xs[i] % self.local[i]) * self.face_stride[a][i]
            p = self.face_offset[a] + (B * 2 + j) * self.face_size[a] + r
            pos = xp.where(on, p, pos)
            is_b = is_b | on
        return is_b, pos

    # incremented on every boundary_coords build; the recompile-regression
    # test pins this to one build per decomposition (PR 9 satellite)
    _coords_builds = 0

    @cached_property
    def boundary_coords(self) -> np.ndarray:
        """(table_size, ndim) int32 global coordinates of every table slot,
        built ONCE per decomposition on the host and passed into the mapped
        programs as a replicated *argument* — an input buffer, not an
        in-graph iota cascade that XLA would constant-fold (rebake) into
        every executable that needs it."""
        BlockDecomp._coords_builds += 1
        return np.asarray(self.slot_coords(np), dtype=np.int32)

    @cached_property
    def boundary_coords_dev(self):
        """`boundary_coords` as a device array (uploaded once per decomp).
        The upload must stay concrete even when the first access happens
        inside someone else's trace (the serve engine jits the batch entry
        points) — caching a staged constant here would leak a tracer into
        every later caller."""
        with jax.ensure_compile_time_eval():
            return jnp.asarray(self.boundary_coords)

    def slot_coords(self, xp=jnp):
        """(table_size, ndim) global coordinates of every table slot.
        Prefer the cached `boundary_coords` host array: tracing this with
        xp=jnp bakes the O(table_size * ndim) constant into every
        executable."""
        parts = []
        for a in range(self.k):
            F = self.face_size[a]
            n = self.nblocks * 2 * F
            s = xp.arange(n, dtype=np.int32)
            B, j, r = s // (2 * F), (s % (2 * F)) // F, s % F
            cols = []
            for i in range(self.ndim):
                if i == a:
                    c = ((B // self.bstride[a]) % self.layout[a]
                         * self.local[a] + j * (self.local[a] - 1))
                else:
                    c = (r // self.face_stride[a][i]) % self.local[i]
                    if i < self.k:
                        c = ((B // self.bstride[i]) % self.layout[i]
                             * self.local[i] + c)
                cols.append(c)
            parts.append(xp.stack(cols, axis=1))
        return xp.concatenate(parts, axis=0)


@lru_cache(maxsize=128)
def _decomp_cached(grid, layout, names) -> BlockDecomp:
    return BlockDecomp(grid, layout, names)


def _decomp_for(mesh: Mesh, grid_shape) -> BlockDecomp:
    """Memoized per (grid, layout): repeated calls on the same geometry
    share one BlockDecomp, so `boundary_coords` (and the sharded-stack
    geometry) are built once, not per request."""
    names = tuple(mesh.axis_names)
    layout = tuple(mesh.shape[n] for n in names)
    return _decomp_cached(tuple(int(x) for x in grid_shape), layout, names)


_check_table_mode = check_table_mode  # shared with the graph backend


# --- shared traced helpers ---------------------------------------------------


def _owned_valid(dec: BlockDecomp):
    """Boolean owned-block array marking in-domain (non-pad) cells, from the
    block's position on the mesh (deviation (p) in DESIGN.md)."""
    total = None
    for a in range(dec.k):
        b = lax.axis_index(dec.names[a])
        x = b * dec.local[a] + jnp.arange(dec.local[a], dtype=jnp.int32)
        shape = [1] * dec.ndim
        shape[a] = -1
        v = (x < dec.grid[a]).reshape(shape)
        total = v if total is None else total & v
    return jnp.broadcast_to(total, dec.local)


def _halo_extend(ext, dim, name, n_blocks, fill):
    """Extend `ext` with one ghost slab per face along grid axis `dim`,
    exchanged over mesh axis `name` (fill at the domain boundary).  Applied
    axis-by-axis, so later axes forward earlier ghosts into the corners."""
    lo_src = lax.index_in_dim(ext, ext.shape[dim] - 1, dim, keepdims=True)
    hi_src = lax.index_in_dim(ext, 0, dim, keepdims=True)
    if n_blocks == 1:
        lo = jnp.full_like(lo_src, fill)
        hi = jnp.full_like(hi_src, fill)
    else:
        p = lax.axis_index(name)
        lo = collective(lax.ppermute, lo_src, name,
                        [(i, i + 1) for i in range(n_blocks - 1)])
        hi = collective(lax.ppermute, hi_src, name,
                        [(i + 1, i) for i in range(n_blocks - 1)])
        lo = jnp.where(p == 0, fill, lo)
        hi = jnp.where(p == n_blocks - 1, fill, hi)
    return jnp.concatenate([lo, ext, hi], axis=dim)


def _to_global(d, dec: BlockDecomp):
    """Global flat ids of extended-block local ids `d` (>= 0), by coordinate
    arithmetic on `d` itself — no block-sized id map is built, so nothing
    block-sized can be constant-folded into the program (out-of-domain
    ghost coordinates give ids that are never read: their order/mask fill
    keeps them off every pointer path)."""
    d = d.astype(dec.id_dtype)
    g = None
    for i in reversed(range(dec.ndim)):
        c = d % dec.ext[i]
        d = d // dec.ext[i]
        if i < dec.k:
            b = lax.axis_index(dec.names[i]).astype(dec.id_dtype)
            c = b * dec.local[i] - 1 + c
        part = c * dec.stride[i]
        g = part if g is None else g + part
    return g


def _gather_table(owned, dec: BlockDecomp):
    """The single communication phase: all_gather every block's owned lo/hi
    face along each decomposed axis into one replicated flat table laid out
    as BlockDecomp.boundary_pos expects."""
    with jax.named_scope("dpc.table.gather"):
        parts = []
        for a in range(dec.k):
            lo = lax.index_in_dim(owned, 0, a, keepdims=False)
            hi = lax.index_in_dim(owned, dec.local[a] - 1, a, keepdims=False)
            bt = jnp.stack([lo.reshape(-1), hi.reshape(-1)])   # (2, F_a)
            g = collective(lax.all_gather, bt, dec.names)  # (nblocks, 2, F_a)
            parts.append(g.reshape(-1))
        return jnp.concatenate(parts)


def _own_faces(owned, dec: BlockDecomp):
    """This device's own row-chunk of the boundary table: the block's lo/hi
    face along each decomposed axis, flattened exactly like one block's
    segment of the gathered table (`row = local_face_offset[a] + j*F_a + r`).
    `_gather_table` == all_gather of every block's `_own_faces`."""
    with jax.named_scope("dpc.table.gather"):
        parts = []
        for a in range(dec.k):
            lo = lax.index_in_dim(owned, 0, a, keepdims=False)
            hi = lax.index_in_dim(owned, dec.local[a] - 1, a, keepdims=False)
            parts.append(
                jnp.stack([lo.reshape(-1), hi.reshape(-1)]).reshape(-1))
        return jnp.concatenate(parts)


def _table_compress(T, dec: BlockDecomp, max_iter=64):
    """Pointer doubling on the gathered flat table (Alg. 2 lines 15-25).
    Entries < 0 (unmasked CC cells and the pad sentinels of deviation (p))
    and non-boundary targets are fixed.  The slot lookup is pure coordinate
    arithmetic (boundary_pos); the chase itself is the shared
    backend-agnostic loop in core/_table.py.  Returns (table, iters, ok)."""
    def lookup(t):
        is_b, pos = dec.boundary_pos(jnp.clip(t, 0), jnp)
        tv = t[jnp.clip(pos, 0, t.size - 1)]
        return jnp.where((t >= 0) & is_b, tv, t)

    view, iters, ok = chase_view(TableView(T, lookup, T.size), max_iter)
    return view.values, iters, ok


# --- sharded boundary table (table_mode="sharded", deviation (s)) ------------


class _ShardGeom:
    """Static geometry of the sharded boundary-table stack (deviation (s) in
    DESIGN.md §Table-sharding).

    Per device the stack is `n_chunks` copies of the per-block face-row
    layout (`rows` = both faces of every decomposed axis, `_own_faces`
    order): chunk 0 is the device's OWN faces, the rest a one-hop halo of
    lattice-neighbor blocks.  Axes with layout 1 contribute no halo; layout
    2 contributes ONE chunk (the swap partner is both the +1 and the -1
    neighbor); layout >= 3 contributes lo/hi chunks, with lattice-edge
    positions filled by inert sentinels (label -1 / mask False, the
    deviation-(p) contract).  When the stencil reaches no diagonal block
    pair (e.g. connectivity 6 on a 3-D lattice) the chunk set is the
    von-Neumann star (1 + sum(sz-1) chunks); otherwise the full Moore
    product (prod(sz)) is built by dimension-ordered forwarding, exactly
    like the ghost halo itself.
    """

    def __init__(self, dec: BlockDecomp, connectivity: int):
        self.dec = dec
        self.rows = dec.table_size // dec.nblocks
        self.local_off = [dec.face_offset[a] // dec.nblocks
                          for a in range(dec.k)]
        self.act = [a for a in range(dec.k) if dec.layout[a] > 1]
        self.sz = {a: (2 if dec.layout[a] == 2 else 3) for a in self.act}
        offs = neighbor_offsets(dec.ndim, connectivity)
        self.moore = any(
            sum(1 for a in self.act if off[a] != 0) >= 2 for off in offs)
        if self.moore:
            self.n_chunks = math.prod(self.sz[a] for a in self.act)
        else:
            self.n_chunks, self.vn_base = 1, {}
            for a in self.act:
                self.vn_base[a] = self.n_chunks
                self.n_chunks += self.sz[a] - 1
        self.stack_size = self.n_chunks * self.rows

    def exchange_fn(self, fill):
        """One halo-exchange round: own chunk -> flat (stack_size,) stack
        with the own chunk leading (`sharded_fixpoint` contract).  The Moore
        variant forwards the partial stack axis-by-axis so diagonal-neighbor
        chunks arrive via two axis hops (`_halo_extend`'s argument)."""
        dec = self.dec

        def axis_parts(src, a):
            L, name = dec.layout[a], dec.names[a]
            if L == 2:
                return [collective(lax.ppermute, src, name, [(0, 1), (1, 0)])]
            lo = collective(lax.ppermute, src, name,
                            [(i, i + 1) for i in range(L - 1)])
            hi = collective(lax.ppermute, src, name,
                            [(i + 1, i) for i in range(L - 1)])
            p = lax.axis_index(name)
            return [jnp.where(p == 0, fill, lo),
                    jnp.where(p == L - 1, fill, hi)]

        if self.moore:
            def exchange(own):
                S, dims = own, 0
                for a in self.act:
                    S = jnp.stack([S] + axis_parts(S, a), axis=dims)
                    dims += 1
                return S.reshape(-1)
        else:
            def exchange(own):
                chunks = [own]
                for a in self.act:
                    chunks.extend(axis_parts(own, a))
                return jnp.concatenate(chunks) if len(chunks) > 1 else own

        def scoped(own):
            with jax.named_scope("dpc.table.gather"):
                return exchange(own)
        return scoped

    def pos_to_stack(self, s):
        """Global table slot -> (in_stack, flat stack index).  Callers gate
        on `is_boundary` (and validity) before trusting either output."""
        dec = self.dec
        row = jnp.zeros_like(s)
        B = jnp.zeros_like(s)
        for a in range(dec.k):
            F2 = 2 * dec.face_size[a]
            off = dec.face_offset[a]
            within = (s >= off) & (s < off + dec.nblocks * F2)
            t = jnp.where(within, s - off, 0)
            row = jnp.where(within, self.local_off[a] + t % F2, row)
            B = jnp.where(within, t // F2, B)
        row = row.astype(jnp.int32)
        B = B.astype(jnp.int32)
        ok = jnp.ones_like(row, dtype=bool)
        chunk = jnp.zeros_like(row)
        nnz = jnp.zeros_like(row)
        pos = {}
        for a in self.act:
            c = (B // dec.bstride[a]) % dec.layout[a]
            d = c - lax.axis_index(dec.names[a])
            if dec.layout[a] == 2:
                pa = (d != 0).astype(jnp.int32)
            else:
                ok = ok & (jnp.abs(d) <= 1)
                pa = jnp.where(d == 0, 0, jnp.where(d == -1, 1, 2))
            pos[a] = pa
            nnz = nnz + (pa > 0)
        if self.moore:
            for a in self.act:
                chunk = chunk * self.sz[a] + pos[a]
        else:
            ok = ok & (nnz <= 1)
            for a in self.act:
                chunk = chunk + jnp.where(pos[a] > 0,
                                          self.vn_base[a] + pos[a] - 1, 0)
        return ok, chunk * self.rows + row

    def lookup_fn(self):
        """Value lookup through the stack (the sharded TableView lookup):
        in-stack boundary targets map through, everything else is fixed."""
        dec, size = self.dec, self.stack_size

        def lookup(t):
            is_b, s = dec.boundary_pos(jnp.clip(t, 0), jnp)
            ok, idx = self.pos_to_stack(s)
            tv = t[jnp.clip(idx, 0, size - 1)]
            return jnp.where((t >= 0) & is_b & ok, tv, t)
        return lookup

    def _chunk_block_coords(self, ci: int):
        """Traced per-axis block coordinates of (static) chunk `ci`."""
        dec = self.dec
        pos = {a: 0 for a in range(dec.k)}
        if self.moore:
            rest = ci
            for a in reversed(self.act):
                pos[a] = rest % self.sz[a]
                rest //= self.sz[a]
        else:
            for a in self.act:
                if self.vn_base[a] <= ci < self.vn_base[a] + self.sz[a] - 1:
                    pos[a] = ci - self.vn_base[a] + 1
        bc = []
        for a in range(dec.k):
            p = lax.axis_index(dec.names[a])
            if pos[a] == 0:
                bc.append(p)
            elif dec.layout[a] == 2:
                bc.append(1 - p)            # the swap partner
            else:
                bc.append(p - 1 if pos[a] == 1 else p + 1)
        return bc

    def stack_coords(self, coords):
        """(stack_size, ndim) global coordinates of every stack slot plus a
        per-slot validity mask (False on lattice-edge fill chunks).  Rows are
        gathered per chunk from the cached `boundary_coords` table — passed
        in as a traced argument, never baked."""
        dec = self.dec
        r_i = jnp.arange(self.rows, dtype=jnp.int32)
        parts, valids = [], []
        for ci in range(self.n_chunks):
            bc = self._chunk_block_coords(ci)
            valid, B = None, jnp.int32(0)
            for a in range(dec.k):
                v = (bc[a] >= 0) & (bc[a] < dec.layout[a])
                valid = v if valid is None else valid & v
                B = B + jnp.clip(bc[a], 0, dec.layout[a] - 1) * dec.bstride[a]
            gidx = jnp.zeros_like(r_i)
            for a in range(dec.k):
                lo = self.local_off[a]
                F2 = 2 * dec.face_size[a]
                within = (r_i >= lo) & (r_i < lo + F2)
                gidx = jnp.where(
                    within, dec.face_offset[a] + B * F2 + (r_i - lo), gidx)
            parts.append(coords[gidx])
            valids.append(jnp.broadcast_to(valid, (self.rows,)))
        return jnp.concatenate(parts), jnp.concatenate(valids)


def _shard_geom_for(dec: BlockDecomp, connectivity: int) -> _ShardGeom:
    cache = dec.__dict__.setdefault("_shard_geoms", {})
    key = int(connectivity)
    if key not in cache:
        cache[key] = _ShardGeom(dec, connectivity)
    return cache[key]


def _preduce_stats(dec: BlockDecomp, iters, rounds, ok):
    """Mesh-wide reductions of per-device sharded fixpoint stats."""
    return (collective(lax.pmax, iters, dec.names), rounds,
            collective(lax.pmin, ok.astype(jnp.int32), dec.names))


# --- MS manifolds ------------------------------------------------------------


def _sharded_manifold_resolve(owned, dec: BlockDecomp, connectivity,
                              max_iter: int):
    """Sharded replacement of steps 4-6 (gather + compress + substitute)
    for manifolds: a neighbor-relay fixpoint on the own+halo stack.  Each
    outer round rebuilds the view from fresh estimates and re-chases every
    own slot from its ORIGINAL one-hop pointer through the view (in-view
    segments compress by pointer doubling within the round; the estimate a
    chain adopts at its deepest in-view slot is that neighbor's previous
    round's reach).  Converges to the chains' unique terminals — the exact
    values the replicated chase produces (DESIGN.md §Table-sharding)."""
    geom = _shard_geom_for(dec, connectivity)
    T0 = _own_faces(owned, dec)
    lookup = geom.lookup_fn()
    exchange = geom.exchange_fn(-1)

    def refine(stack):
        view = TableView(stack.at[:geom.rows].set(T0), lookup, geom.rows)
        view, iters, ok = chase_view(view, max_iter)
        return view.values, iters, ok

    def reduce_any(x):
        return collective(lax.pmax, x.astype(jnp.int32), dec.names) > 0

    stackT, _, rounds, iters, ok = sharded_fixpoint(
        T0, exchange, refine, reduce_any, max_rounds=max_iter)

    with jax.named_scope("dpc.table.substitute"):
        o = owned.ravel()
        is_b, s = dec.boundary_pos(jnp.clip(o, 0), jnp)
        is_b, okp, idx = materialize((is_b, *geom.pos_to_stack(s)))
        final = jnp.where((o >= 0) & is_b & okp,
                          stackT[jnp.clip(idx, 0, geom.stack_size - 1)], o)
    return final, geom, rounds, iters, ok


def _manifold_block(order_blk, *, dec: BlockDecomp, connectivity,
                    fused_impl: str = "auto", table_mode: str = "replicated",
                    table_max_iter: int = 64):
    """Always runs the *descending* direction; the ascending manifold is
    obtained by flipping the order field outside (keeps the -1 halo fill
    strictly below every candidate)."""
    # lazy: repro.kernels imports repro.core.steepest at module load
    from repro.kernels.ops import fused_local_phase

    # 1. order halo (fill -1: below every real order value, never steepest)
    ext = order_blk
    with jax.named_scope("dpc.halo"):
        for a in range(dec.k):
            ext = _halo_extend(ext, a, dec.names[a], dec.layout[a], -1)

    # 2. steepest init in local ids, the ghost layers (first/last layer of
    #    every decomposed axis) pretending to be maxima (Alg. 1 lines 6-8)
    with jax.named_scope("dpc.init"):
        d = fused_local_phase(ext, connectivity, mode="manifold",
                              ghost_axes=tuple(range(dec.k)),
                              impl=fused_impl).ravel()

    # 3. local compression to the block fixpoint (Alg. 1 lines 9-19)
    d, local_iters = path_compress(d)

    # 4. to global ids + the single communication phase (Alg. 2); pad cells
    #    of a ragged block carry the sentinel -1, which the chase fixes and
    #    the substitution skips (deviation (p) in DESIGN.md)
    with jax.named_scope("dpc.ids"):
        owned = _to_global(d.reshape(dec.ext)[dec.owned_slices], dec)
        if dec.ragged:
            owned = jnp.where(_owned_valid(dec), owned, dec.id_dtype(-1))
    isz = np.dtype(dec.id_dtype).itemsize

    if table_mode == "replicated":
        # 4. the single communication phase (Alg. 2) + 5. ghost-table
        #    compression (identical on every device)
        T = _gather_table(owned, dec)
        T, table_iters, chase_ok = _table_compress(T, dec, table_max_iter)

        # 6. final substitution (Alg. 2 lines 27-33)
        with jax.named_scope("dpc.table.substitute"):
            o = owned.ravel()
            is_b, pos = materialize(dec.boundary_pos(jnp.clip(o, 0), jnp))
            final = jnp.where((o >= 0) & is_b,
                              T[jnp.clip(pos, 0, T.size - 1)], o)
        comm = jnp.int32(1)
        exch_rounds = jnp.int32(0)
        ghost_bytes = jnp.float32(dec.n_valid_slots * isz)
        table_bytes = jnp.float32(dec.table_size * isz)
        converged = chase_ok.astype(jnp.int32)
    else:
        # 4-6. sharded: own faces + one-hop halo, neighbor-relay fixpoint
        with jax.named_scope("dpc.table"):
            final, geom, exch_rounds, iters, ok = _sharded_manifold_resolve(
                owned, dec, connectivity, table_max_iter)
        table_iters, _, converged = _preduce_stats(dec, iters, exch_rounds,
                                                   ok)
        comm = exch_rounds                 # one exchange phase per round
        halo = geom.stack_size - geom.rows
        ghost_bytes = jnp.float32(halo * isz) * exch_rounds.astype(
            jnp.float32)
        table_bytes = jnp.float32((geom.stack_size + geom.rows) * isz)

    stats = DPCStats(
        local_iters=collective(lax.pmax, local_iters, dec.names),
        table_iters=table_iters,
        stitch_rounds=jnp.int32(0),
        ghost_bytes=ghost_bytes,
        masked_ghost_fraction=jnp.float32(1.0),
        pad_fraction=jnp.float32(dec.pad_fraction),
        comm_phases=comm,
        table_bytes_peak=table_bytes,
        exchange_rounds=exch_rounds,
        converged=converged,
    )
    return final.reshape(order_blk.shape), stats


@partial(jax.jit, static_argnums=1)
def _flip_order(order, n: int):
    """The ascending manifold is the descending one of the flipped order
    field n - 1 - order (n vertices); jitted, so its op carries its scope
    into the device trace."""
    with jax.named_scope("dpc.order.flip"):
        return n - 1 - order


def distributed_manifold(order, mesh: Mesh, connectivity: int = 6,
                         descending: bool = True, fused_impl: str = "auto",
                         table_mode: str = "replicated",
                         table_max_iter: int = 64):
    """Descending (or ascending) manifold of a block-sharded order field.

    order: int array of ANY extent (mesh axis a decomposes grid axis a;
    non-divisible extents are padded with inert sentinels, deviation (p) in
    DESIGN.md).  Returns the label grid (same extent as `order`) and
    replicated DPCStats.  fused_impl selects the block-local phase
    implementation (repro.kernels.ops.fused_local_phase); table_mode picks
    the boundary-table layout — "replicated" (one all_gather) or "sharded"
    (own faces + one-hop halo, outer exchange rounds; deviation (s)); labels
    are bit-identical across all choices.
    """
    _check_table_mode(table_mode)
    prog = _grid_program("manifold", mesh, tuple(order.shape), False,
                         connectivity, True, fused_impl, table_mode,
                         table_max_iter)
    if not descending:
        order = _flip_order(order, order.size)
    with jax.profiler.TraceAnnotation("dpc.dispatch"):
        labels, stats = prog(order)
    check_converged(stats.converged, "distributed_manifold", table_max_iter)
    return labels, stats


# --- connected components ----------------------------------------------------


def _cc_local_fixpoint(d, mask_ext, connectivity, max_rounds=64):
    d, its0 = path_compress(d)
    sentinel = d.size

    def cond(s):
        _, ch, r, _ = s
        return ch & (r < max_rounds)

    def body(s):
        cur, _, r, its = s
        st = grid_stitch(cur, mask_ext, connectivity, sentinel)
        nxt, it = path_compress(st)
        return nxt, jnp.any(nxt != cur), r + jnp.int32(1), its + it

    with jax.named_scope("dpc.cc_stitch"):
        d, _, rounds, its = lax.while_loop(
            cond, body, (d, jnp.asarray(True), jnp.int32(0), its0))
    return d, rounds, its


def _table_propagate(Tstar, Mflat, coords, dec: BlockDecomp, connectivity,
                     max_iter=64):
    """Hook + propagate on the gathered flat table: fixpoint of
      (a) max across masked stencil edges between boundary vertices (slot
          adjacency derived arithmetically per round — covers axis cuts and
          diagonal block pairs without a precomputed table),
      (b) max within equal-original-label groups (sorted-runs segment_max).
    Computes, for every boundary slot, the largest label of its global
    component.  Deviation (d2): the paper's path compression alone cannot
    perform these merges.  The group machinery and the fixpoint loop are
    shared with the unstructured backend (core/_table.py); only `cut_max`
    — slot adjacency by coordinate arithmetic — is block-specific.
    `coords` is the cached (table_size, ndim) slot-coordinate table, passed
    in as a traced argument (see BlockDecomp.boundary_coords)."""
    msize = Tstar.size
    group_max, perm, sorted_vals = make_group_max(Tstar)

    grid = jnp.asarray(dec.grid, dtype=jnp.int32)
    stride = jnp.asarray(dec.stride, dtype=dec.id_dtype)
    offsets = neighbor_offsets(dec.ndim, connectivity)

    def cut_max(L):
        best = L
        for off in offsets:
            nx = coords + jnp.asarray(off, dtype=jnp.int32)
            valid = jnp.all((nx >= 0) & (nx < grid), axis=1)
            g = (jnp.clip(nx, 0, grid - 1).astype(dec.id_dtype)
                 * stride).sum(axis=1)
            is_b, pos = dec.boundary_pos(g, jnp)
            ok = valid & is_b
            safe = jnp.clip(pos, 0, msize - 1)
            nl = jnp.where(ok, L[safe], -1)
            nm = jnp.where(ok, Mflat[safe], False)
            best = jnp.where(Mflat & nm, jnp.maximum(best, nl), best)
        return best

    L, iters, ok = hook_propagate(Tstar, cut_max, group_max, max_iter)
    return L, (perm, sorted_vals), iters, ok


def _sharded_cc_resolve(owned, mask_owned, coords, dec: BlockDecomp,
                        connectivity, gather_mask: bool, max_iter: int):
    """Sharded replacement of CC steps 4-6: a max-flooding fixpoint on the
    own+halo stack.  No chase stage is needed — the flood relation (masked
    stencil cut edges between in-stack slots + equal-ORIGINAL-label groups
    within the stack) connects exactly the slots of each global component,
    and its unique monotone fixpoint is the component's max vertex id, the
    same value the replicated chase+hook+propagate computes (DESIGN.md
    §Table-sharding).  The static label/mask stacks are exchanged once
    (building the per-device group structure); each outer round then
    exchanges only the evolving estimates."""
    geom = _shard_geom_for(dec, connectivity)
    T0 = _own_faces(owned, dec)
    exchange = geom.exchange_fn(-1)
    T0s = exchange(T0)                       # static: group structure
    if gather_mask:
        Ms = geom.exchange_fn(False)(_own_faces(mask_owned, dec))
    else:
        Ms = T0s >= 0                        # labels are -1 iff unmasked
    group_max, perm, sorted_vals = make_group_max(T0s)

    scoords, svalid = geom.stack_coords(coords)
    grid = jnp.asarray(dec.grid, dtype=jnp.int32)
    stride = jnp.asarray(dec.stride, dtype=dec.id_dtype)
    offsets = neighbor_offsets(dec.ndim, connectivity)

    def cut_max(L):
        best = L
        for off in offsets:
            nx = scoords + jnp.asarray(off, dtype=jnp.int32)
            valid = jnp.all((nx >= 0) & (nx < grid), axis=1) & svalid
            g = (jnp.clip(nx, 0, grid - 1).astype(dec.id_dtype)
                 * stride).sum(axis=1)
            is_b, s = dec.boundary_pos(g, jnp)
            okn, idx = geom.pos_to_stack(s)
            ok = valid & is_b & okn
            safe = jnp.clip(idx, 0, geom.stack_size - 1)
            nl = jnp.where(ok, L[safe], -1)
            nm = jnp.where(ok, Ms[safe], False)
            best = jnp.where(Ms & nm, jnp.maximum(best, nl), best)
        return best

    def refine(stack):
        return hook_propagate(stack, cut_max, group_max, max_iter)

    def reduce_any(x):
        return collective(lax.pmax, x.astype(jnp.int32), dec.names) > 0

    stackG, _, rounds, iters, ok = sharded_fixpoint(
        T0, exchange, refine, reduce_any, max_rounds=max_iter)

    # substitution: adopt the flooded value at the own label's slot when it
    # has one, then the value search over the STATIC stack labels (an owned
    # interior root is not a slot but shares its value with its piece's cut
    # vertices, which are in the own chunk whenever the piece reaches a cut)
    with jax.named_scope("dpc.table.substitute"):
        o = owned.ravel()
        is_b, s = dec.boundary_pos(jnp.clip(o, 0), jnp)
        is_b, okp, idx = materialize((is_b, *geom.pos_to_stack(s)))
        chased = jnp.where((o >= 0) & is_b & okp,
                           stackG[jnp.clip(idx, 0, geom.stack_size - 1)], o)
    final = value_substitute(o, chased, sorted_vals, stackG[perm])
    return final, Ms, geom, rounds, iters, ok


def _cc_block(mask_blk, coords=None, *, dec: BlockDecomp, connectivity,
              gather_mask: bool = True, fused_impl: str = "auto",
              table_mode: str = "replicated", table_max_iter: int = 64):
    """gather_mask=False is the §Perf variant: the boundary mask is exactly
    (T >= 0) — labels are -1 where unmasked — so the mask all-gather is
    redundant and dropped (less exchange traffic, bit-identical).

    `coords` is the decomposition's boundary slot-coordinate table; the
    public entry points thread `dec.boundary_coords_dev` through the
    shard_map as an argument so the O(table_size * ndim) constant is not
    rebaked into every executable.  Direct internal callers may omit it —
    the fallback closes over the cached constant (old behaviour)."""
    if coords is None:
        coords = dec.boundary_coords_dev
    # lazy: repro.kernels imports repro.core.steepest at module load
    from repro.kernels.ops import fused_local_phase

    # 1. mask halo (fill False: domain boundary is never masked)
    ext = mask_blk
    with jax.named_scope("dpc.halo"):
        for a in range(dec.k):
            ext = _halo_extend(ext, a, dec.names[a], dec.layout[a], False)

    # 2. init: largest masked neighbor id, masked ghosts pretending self
    with jax.named_scope("dpc.init"):
        d = fused_local_phase(ext, connectivity, mode="cc",
                              ghost_axes=tuple(range(dec.k)),
                              impl=fused_impl).ravel()

    # 3. local CC fixpoint (stitch + compress, Alg. 3)
    d, stitch_rounds, local_iters = _cc_local_fixpoint(
        d, ext, connectivity)

    # 4. to global ids
    with jax.named_scope("dpc.ids"):
        do = d.reshape(dec.ext)[dec.owned_slices]
        owned = jnp.where(do >= 0, _to_global(jnp.clip(do, 0), dec),
                          dec.id_dtype(-1))
    isz = np.dtype(dec.id_dtype).itemsize

    if table_mode == "replicated":
        # 4b. the single communication phase: labels (+ masks)
        T = _gather_table(owned, dec)
        if gather_mask:
            M = _gather_table(ext[dec.owned_slices], dec)
        else:
            M = T >= 0             # labels are -1 exactly where unmasked

        # 5a. positional chase (the paper's table compression — resolves
        #     chains through ghost labels, e.g. a part labeled with a
        #     ghost's id)
        Tstar, table_iters, chase_ok = _table_compress(T, dec,
                                                       table_max_iter)
        # 5b. hook + propagate (deviation (d2)): merge labels across cuts
        G, (perm, sorted_vals), prop_iters, prop_ok = _table_propagate(
            Tstar, M, coords, dec, connectivity, table_max_iter)

        # 6. substitution: chase own label through the table, then take its
        #    group's propagated maximum (value search over the sorted table)
        with jax.named_scope("dpc.table.substitute"):
            o = owned.ravel()
            is_b, pos = materialize(dec.boundary_pos(jnp.clip(o, 0), jnp))
            chased = jnp.where((o >= 0) & is_b,
                               Tstar[jnp.clip(pos, 0, Tstar.size - 1)], o)
        final = value_substitute(o, chased, sorted_vals, G[perm])

        table_iters = table_iters + prop_iters
        comm = jnp.int32(1)
        exch_rounds = jnp.int32(0)
        converged = (chase_ok & prop_ok).astype(jnp.int32)
        ghost_bytes = (jnp.float32(dec.n_valid_slots * isz)
                       + (jnp.float32(dec.n_valid_slots) if gather_mask
                          else 0.0))
        table_bytes = jnp.float32(dec.table_size * (isz + 1))
        masked_frac = (jnp.sum(M).astype(jnp.float32)
                       / jnp.float32(max(dec.n_valid_slots, 1)))
    else:
        # 4b-6. sharded: max-flooding on the own+halo stack (no gather)
        with jax.named_scope("dpc.table"):
            final, Ms, geom, exch_rounds, iters, ok = _sharded_cc_resolve(
                owned, ext[dec.owned_slices], coords, dec, connectivity,
                gather_mask, table_max_iter)
        table_iters, _, converged = _preduce_stats(dec, iters, exch_rounds,
                                                   ok)
        comm = exch_rounds + jnp.int32(1)  # +1: the static label/mask stack
        halo = geom.stack_size - geom.rows
        ghost_bytes = (jnp.float32(halo * isz)
                       * (exch_rounds.astype(jnp.float32) + 1.0)
                       + (jnp.float32(halo) if gather_mask else 0.0))
        # evolving stack + static label stack + own chunk + bool mask stack
        table_bytes = jnp.float32((2 * geom.stack_size + geom.rows) * isz
                                  + geom.stack_size)
        # global fraction over in-domain slots (== the replicated number:
        # pad slots are mask-False on both paths, deviation (p))
        masked_frac = (collective(
            lax.psum, jnp.sum(Ms[:geom.rows]).astype(jnp.float32),
            dec.names) / jnp.float32(max(dec.n_valid_slots, 1)))

    # pad table slots are label -1 / mask False by construction (the input
    # mask is padded False, deviation (p)), so they are excluded here
    stats = DPCStats(
        local_iters=collective(lax.pmax, local_iters, dec.names),
        table_iters=table_iters,
        stitch_rounds=collective(lax.pmax, stitch_rounds, dec.names),
        ghost_bytes=ghost_bytes,
        masked_ghost_fraction=masked_frac,
        pad_fraction=jnp.float32(dec.pad_fraction),
        comm_phases=comm,
        table_bytes_peak=table_bytes,
        exchange_rounds=exch_rounds,
        converged=converged,
    )
    return final.reshape(mask_blk.shape), stats


def distributed_connected_components(mask, mesh: Mesh, connectivity: int = 6,
                                     gather_mask: bool = True,
                                     fused_impl: str = "auto",
                                     table_mode: str = "replicated",
                                     table_max_iter: int = 64):
    """Mask-implicit connected components of a block-sharded grid (Alg. 3 +
    Alg. 2).  Any grid extent works: non-divisible extents are padded with
    mask=False sentinels, which are inert in every phase (deviation (p) in
    DESIGN.md).  Returns (labels, DPCStats); labels carry the largest vertex
    id of the component, -1 where unmasked.  gather_mask=False drops the
    redundant mask exchange (§Perf); fused_impl selects the block-local
    phase implementation; table_mode="sharded" keeps the boundary table
    distributed (deviation (s)).  Labels are bit-identical across all
    choices."""
    _check_table_mode(table_mode)
    prog = _grid_program("cc", mesh, tuple(mask.shape), False, connectivity,
                         gather_mask, fused_impl, table_mode, table_max_iter)
    coords = _decomp_for(mesh, mask.shape).boundary_coords_dev
    with jax.profiler.TraceAnnotation("dpc.dispatch"):
        labels, stats = prog(mask, coords)
    check_converged(stats.converged, "distributed_connected_components",
                    table_max_iter)
    return labels, stats


# --- batched (multi-tenant) entry points --------------------------------------
# One shard_map over a request-leading batch dim: the per-block program is
# vmapped, so the halo ppermutes and the ONE boundary all_gather each fire
# once for the whole batch — compilation AND the communication phase are
# amortised across tenants (the serving-engine contract, DESIGN.md §Serve).
# Labels are bit-identical per item to the single-request entry points; the
# returned DPCStats carry a leading (B,) request dim.


@lru_cache(maxsize=64)
def _grid_program(kind, mesh: Mesh, grid, batched, connectivity,
                  gather_mask, fused_impl, table_mode, table_max_iter):
    """One jitted SPMD program per (query kind, mesh, grid extent, knobs),
    shared by every call with the same key (both manifold directions: the
    callers flip the order field for ascending ones): the pad-and-mask
    input padding (deviation (p)), the `shard_map` of the per-block program
    and the unpadding all compile together.  ``batched`` vmaps the block
    program over a leading request dim (stats then carry a (B,) dim).  The
    cc program takes the boundary slot-coordinate table as a second,
    replicated argument."""
    dec = _decomp_for(mesh, grid)
    if kind == "manifold":
        fn = partial(_manifold_block, dec=dec, connectivity=connectivity,
                     fused_impl=fused_impl, table_mode=table_mode,
                     table_max_iter=table_max_iter)
        fill, extra = -1, ()           # -1: below every real order value
    else:
        fn = partial(_cc_block, dec=dec, connectivity=connectivity,
                     gather_mask=gather_mask, fused_impl=fused_impl,
                     table_mode=table_mode, table_max_iter=table_max_iter)
        fill, extra = False, (P(None, None),)   # padding is never masked

    def mapped(lead):
        spec = P(*lead, *dec.names, *([None] * (dec.ndim - dec.k)))
        f = fn if not lead else jax.vmap(fn, in_axes=(0,) + (None,) * len(
            extra))
        return shard_map_norep(f, mesh, (spec,) + extra,
                               (spec, DPCStats(*([P(*lead)] * _N_STATS))))

    one = mapped(())
    many = mapped((None,)) if batched else None
    lead_axes = 1 if batched else 0

    def run(x, *args):
        if dec.ragged:
            pads = [(0, 0)] * lead_axes + [(0, dec.padded[i] - dec.grid[i])
                                           for i in range(dec.ndim)]
            with jax.named_scope("dpc.ids"):
                x = jnp.pad(x, pads, constant_values=fill)
        if not batched:
            labels, stats = one(x, *args)
        elif x.shape[0] == 1:
            # a batch of one runs the unbatched program: vmap over a
            # single item buys nothing, and the vmapped cc block (its
            # while loops around scatters) takes the TPU compiler ~10x
            # longer
            labels, stats = jax.tree.map(lambda a: a[None],
                                         one(x[0], *args))
        else:
            labels, stats = many(x, *args)
        if dec.ragged:
            with jax.named_scope("dpc.ids"):
                labels = labels[(slice(None),) * lead_axes
                                + tuple(slice(0, g) for g in dec.grid)]
        return labels, stats

    return jax.jit(run)


def distributed_manifold_batch(orders, mesh: Mesh, connectivity: int = 6,
                               descending: bool = True,
                               fused_impl: str = "auto",
                               table_mode: str = "replicated",
                               table_max_iter: int = 64):
    """Batched `distributed_manifold`: orders is a (B, *grid) stack of order
    fields sharing one extent; returns ((B, *grid) labels, DPCStats with a
    leading (B,) dim).  Per item bit-identical to the single-request call."""
    _check_table_mode(table_mode)
    grid = tuple(orders.shape[1:])
    prog = _grid_program("manifold", mesh, grid, True, connectivity, True,
                         fused_impl, table_mode, table_max_iter)
    if not descending:
        orders = _flip_order(orders, math.prod(grid))
    with jax.profiler.TraceAnnotation("dpc.dispatch"):
        labels, stats = prog(orders)
    check_converged(stats.converged, "distributed_manifold_batch",
                    table_max_iter)
    return labels, stats


def distributed_connected_components_batch(masks, mesh: Mesh,
                                           connectivity: int = 6,
                                           gather_mask: bool = True,
                                           fused_impl: str = "auto",
                                           table_mode: str = "replicated",
                                           table_max_iter: int = 64):
    """Batched `distributed_connected_components`: masks is a (B, *grid)
    stack of feature masks sharing one extent; returns ((B, *grid) labels,
    DPCStats with a leading (B,) dim).  Per item bit-identical to the
    single-request call."""
    _check_table_mode(table_mode)
    grid = tuple(masks.shape[1:])
    prog = _grid_program("cc", mesh, grid, True, connectivity, gather_mask,
                         fused_impl, table_mode, table_max_iter)
    coords = _decomp_for(mesh, grid).boundary_coords_dev
    with jax.profiler.TraceAnnotation("dpc.dispatch"):
        labels, stats = prog(masks, coords)
    check_converged(stats.converged, "distributed_connected_components_batch",
                    table_max_iter)
    return labels, stats
