"""Shared machinery for the boundary-table phase (paper Alg. 2).

Both distributed backends — the N-D block decomposition of structured grids
(`distributed.py`) and the vertex partition of unstructured edge-list meshes
(`distributed_graph.py`) — end their local phase by resolving cross-shard
segments on a flat table of boundary/cut labels.  Two table layouts exist
(deviation (s) in DESIGN.md):

  * **replicated** (deviation (b)): ONE all_gather replicates every owned
    boundary slot on every device; the table is post-processed identically
    everywhere.
  * **sharded**: each device materializes only its OWN slots plus a one-hop
    halo of neighbor slots (a "stack"), and the cross-shard fixpoint runs as
    outer rounds of [halo exchange -> local resolve -> global changed?] —
    see `sharded_fixpoint` below.

The post-processing is backend- and layout-agnostic once two lookups are
fixed:

  * how a *label value* maps to its slot in the device's view (coordinate
    arithmetic for blocks, a sorted-gid search for graphs) — a `lookup`
    closure, bundled with the slot values as a `TableView`;
  * which slots are adjacent across shard cuts — a `cut_max` closure.

This module holds the backend-independent pieces: the pointer-doubling chase
(Alg. 2 lines 15-25), the equal-label group machinery and hook+propagate
fixpoint of deviation (d2) in DESIGN.md, the value-search substitution
(Alg. 2 lines 27-33 generalised to merged labels), and the sharded outer
exchange driver.

Sentinel contract (deviation (p) in DESIGN.md): ragged decompositions pad
their tables with slots whose label is -1 and whose mask is False.
Everything here is sentinel-aware by construction — `pointer_chase` fixes
entries < 0 (the backend `lookup` closures gate on `t >= 0`), the cut hooks
fed to `hook_propagate` gate on the mask (False at padding, so a pad slot
can never hook or be hooked), and `value_substitute` leaves negative labels
untouched — so pad slots can never leak a label into a real component, nor
acquire one.  The sharded halo reuses the same sentinels for lattice-edge
fill chunks.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .stats import host_read


TABLE_MODES = ("replicated", "sharded")


def check_table_mode(table_mode: str) -> None:
    if table_mode not in TABLE_MODES:
        raise ValueError(
            f"table_mode must be one of {TABLE_MODES}, got {table_mode!r}")


class TableView(NamedTuple):
    """One device's view of the boundary/cut table.

    `values` are the flat label slots this device materializes — the FULL
    gathered table in replicated mode, own slots followed by the one-hop
    halo stack in sharded mode (the own chunk is ALWAYS `values[..., :n_own]`
    along the last axis; batched entry points carry leading dims).
    `lookup(t)` maps label values through the view: value -> slot in this
    view -> entry at that slot, identity where the value has no slot here
    (non-boundary targets, unresolvable `< 0` entries, out-of-view slots in
    sharded mode).
    """
    values: jax.Array
    lookup: Callable
    n_own: int


def pointer_chase(T, lookup, max_iter: int = 64):
    """Pointer doubling on a flat table (Alg. 2 lines 15-25).

    `lookup(t)` maps every entry of the current table `t` through the table
    itself (entry value -> slot -> entry at that slot), leaving unresolvable
    entries (unmasked `< 0`, non-boundary targets) fixed.  Iterates to the
    fixpoint; returns (compressed table, rounds executed, converged).
    `converged` is False when the loop was cut off at `max_iter` with the
    last round still changing entries — the result may then be mid-chain.
    """
    def cond(s):
        _, ch, i = s
        return ch & (i < max_iter)

    def body(s):
        t, _, i = s
        nt = lookup(t)
        return nt, jnp.any(nt != t), i + jnp.int32(1)

    with jax.named_scope("dpc.table.chase"):
        T, ch, iters = lax.while_loop(cond, body,
                                      (T, jnp.asarray(True), jnp.int32(0)))
    return T, iters, ~ch


def chase_view(view: TableView, max_iter: int = 64):
    """`pointer_chase` over a `TableView`; returns (view', iters, converged)."""
    T, iters, ok = pointer_chase(view.values, view.lookup, max_iter)
    return view._replace(values=T), iters, ok


def make_group_max(Tstar):
    """Equal-label group structure of a (compressed) table.

    Slots sharing a label belong to the same (partial) component; groups are
    realised as runs of the sorted table so a group reduction is one
    `segment_max` (sorted-runs trick, no hash table).  Returns
    (group_max fn, perm, sorted_vals); the latter two also drive the final
    value-search substitution.
    """
    msize = Tstar.size
    with jax.named_scope("dpc.table.propagate"):
        # (value, slot) keys are unique, so an unstable two-key sort returns
        # the stable argsort — and compiles far faster for a TPU than a
        # stable sort
        sorted_vals, perm = lax.sort(
            (Tstar, jnp.arange(msize, dtype=jnp.int32)), num_keys=2,
            is_stable=False)
        run_start = jnp.concatenate(
            [jnp.ones((1,), bool), sorted_vals[1:] != sorted_vals[:-1]])
        run_id = jnp.cumsum(run_start) - 1
        inv_perm = jnp.zeros(msize, dtype=jnp.int32).at[perm].set(
            jnp.arange(msize, dtype=jnp.int32))

    def group_max(L):
        gm = jax.ops.segment_max(L[perm], run_id, num_segments=msize)
        return gm[run_id][inv_perm]

    return group_max, perm, sorted_vals


def hook_propagate(Tstar, cut_max, group_max, max_iter: int = 64):
    """Hook + propagate fixpoint on the compressed table (deviation (d2) in
    DESIGN.md): alternate `cut_max` (max across masked cut edges between
    table slots) and `group_max` (max within equal-original-label groups)
    until no label changes.  Computes, per slot, the largest label of its
    *global* component.  The paper compresses the ghost table with path
    compression only; that cannot *merge* components whose local roots are
    interior vertices — this fixpoint can, and stays within the paper's
    single-communication-phase budget (it only post-processes the
    already-gathered table).  Returns (labels, rounds, converged);
    `converged` is False when cut off at `max_iter` mid-flood.
    """
    def cond(st):
        _, ch, i = st
        return ch & (i < max_iter)

    def body(st):
        L, _, i = st
        nxt = group_max(cut_max(L))
        return nxt, jnp.any(nxt != L), i + jnp.int32(1)

    with jax.named_scope("dpc.table.propagate"):
        L, ch, iters = lax.while_loop(
            cond, body, (Tstar, jnp.asarray(True), jnp.int32(0)))
    return L, iters, ~ch


def materialize(x):
    """Materialise block-sized index arithmetic before the gather that
    consumes it.  Fused into the gather, the TPU compiler's code generation
    grows with the array (tens of seconds at 256^3, minutes at 512^3);
    behind the barrier the program compiles in seconds at any size."""
    return lax.optimization_barrier(x)


def value_substitute(o, chased, sorted_vals, g_sorted):
    """Final substitution for CC (Alg. 2 lines 27-33 generalised): take each
    owned label `chased` through the table, then adopt its equal-label
    group's propagated maximum, found by *value* (searchsorted over the
    sorted table) — by value because an owned label can name an interior
    root that is not itself a table slot but shares its value with cut
    vertices of the same local piece.  `o` is the pre-chase label; `< 0`
    (unmasked) entries stay -1.
    """
    with jax.named_scope("dpc.table.substitute"):
        chased = materialize(chased)
        idx = materialize(jnp.clip(jnp.searchsorted(sorted_vals, chased),
                                   0, sorted_vals.shape[0] - 1))
        found = sorted_vals[idx] == chased
        improved = jnp.where(found & (chased >= 0),
                             jnp.maximum(g_sorted[idx], chased), chased)
        return jnp.where(o < 0, -1, improved)


def sharded_fixpoint(own0, exchange, refine, reduce_any, max_rounds: int = 64):
    """Outer halo-exchange driver of the sharded table mode (deviation (s)).

    `own0` is the device's owned slot chunk (last axis = slots; batched
    callers carry leading dims).  `exchange(own) -> stack` rebuilds the
    own+halo view from fresh owned values (the own chunk MUST land at
    `stack[..., :n_own]`); `refine(stack) -> (stack', iters, ok)` resolves
    the view locally (pointer-doubling chase or hook+propagate — both
    saturate *within* the view, so a round relays information one halo hop
    while compressing arbitrarily long in-view segments); `reduce_any`
    reduces a per-device "changed" flag across the mesh (lax.pmax over the
    decomposed axes).  Rounds repeat until no device's owned chunk changes:
    because every refine step only copies/maxes labels monotonically along
    the same chain/component structure the replicated table resolves, the
    unique global fixpoint — and hence the final labels — is bit-identical
    to the replicated mode (DESIGN.md §Table-sharding).

    Returns (stack, own, exchange_rounds, total inner iters, converged).
    The returned stack holds the converged owned chunk plus a FRESH halo of
    the neighbors' converged values (the trailing exchange is counted in
    `exchange_rounds`), so value lookups for the final substitution can read
    it directly.
    """
    n_own = own0.shape[-1]

    def cond(st):
        _, _, ch, r, _, _ = st
        return ch & (r < max_rounds)

    def body(st):
        stack, own, _, r, it, ok = st
        stack2, inner, ok2 = refine(stack)
        new_own = stack2[..., :n_own]
        ch = reduce_any(jnp.any(new_own != own))
        return (exchange(new_own), new_own, ch, r + jnp.int32(1),
                it + inner, ok & ok2)

    init = (exchange(own0), own0, jnp.asarray(True), jnp.int32(1),
            jnp.int32(0), jnp.asarray(True))
    stack, own, ch, rounds, iters, ok = lax.while_loop(cond, body, init)
    return stack, own, rounds, iters, ok & ~ch


def check_converged(flag, what: str, max_iter: int) -> None:
    """Raise eagerly when a table fixpoint was cut off at `max_iter` instead
    of returning a silently-wrong answer (the pre-PR-9 failure mode).

    Under tracing (jit / vmap of the public entry points) the flag is
    abstract and the check is skipped — callers must then consult the
    `converged` stats field themselves.
    """
    try:
        ok = bool(np.all(host_read(flag)))
    except jax.errors.TracerArrayConversionError:
        return
    if not ok:
        raise RuntimeError(
            f"{what}: table resolution did not reach its fixpoint within "
            f"max_iter={max_iter} rounds; labels would be mid-chain/"
            f"mid-flood. Raise `table_max_iter` (the stats field "
            f"`converged` carries the same flag under jit).")
