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


# Owned labels per sort-merge join: the value search joins the table against
# this many labels at a time (64 joins at 512^3), so a join's temporaries stay
# a few tens of MB and its sorts compile to little machine code, which a
# TPU's peak memory counts.
_JOIN_CHUNK = 1 << 21


def _merge_join(q, t_keys, t_delta):
    """Join labels `q` against the sorted table keys `t_keys`: each label
    whose value has a slot takes the result of that value's leftmost slot,
    every other label stays.  `t_delta` is the per-slot result less the next
    slot's (wrapping), so a suffix sum over the merged order reads the result
    of the first slot at or after each label."""
    n, m = q.shape[0], t_keys.shape[0]
    code = lax.iota(jnp.int32, n + m)
    # (key, code) pairs are unique and put a value's labels before its
    # slots, leftmost slot first, so an unstable int32 sort is exact (a
    # stable one compiles far slower for a TPU)
    key_s, code_s, delta_s = lax.sort(
        (jnp.concatenate([q, t_keys]), code,
         jnp.concatenate([jnp.zeros_like(q), t_delta])),
        num_keys=2, is_stable=False)
    # keys ascend, so the running min from the end is the next slot's key
    top = jnp.iinfo(key_s.dtype).max
    next_slot_key = lax.cummin(jnp.where(code_s >= n, key_s, top),
                               reverse=True)
    # a label at the dtype's top value is its own result either way
    found = (next_slot_key == key_s) & (key_s != top)
    joined = jnp.where(found, lax.cumsum(delta_s, reverse=True), key_s)
    _, out = lax.sort((code_s, joined), num_keys=1, is_stable=False)
    return out[:n]


def value_substitute(o, chased, sorted_vals, g_sorted):
    """Final substitution for CC (Alg. 2 lines 27-33 generalised): take each
    owned label `chased` through the table, then adopt its equal-label
    group's propagated maximum, found by *value* — by value because an owned
    label can name an interior root that is not itself a table slot but
    shares its value with cut vertices of the same local piece.  A label
    whose value has slots takes the leftmost slot's `g_sorted` (as a
    left-sided search of `sorted_vals` would).  `o` is the pre-chase label;
    `< 0` (unmasked) entries stay -1.

    The search is a sort-merge join of the labels against the sorted table
    (DESIGN.md §Perf), over static chunks of `_JOIN_CHUNK` labels: a
    binary search would read the table once per halving round, each round a
    block-sized gather.
    """
    with jax.named_scope("dpc.table.substitute"):
        m, n = sorted_vals.shape[0], chased.shape[0]
        # a negative label is left as it is, so o < 0 -> -1 can come first
        q = jnp.where(o < 0, jnp.asarray(-1, chased.dtype), chased)
        res = jnp.where(sorted_vals >= 0,
                        jnp.maximum(g_sorted, sorted_vals), sorted_vals)
        t_delta = res - jnp.concatenate([res[1:], jnp.zeros_like(res[:1])])

        chunk = max(_JOIN_CHUNK, m)
        if n <= chunk:
            return _merge_join(q, sorted_vals, t_delta)
        k = -(-n // chunk)
        size = -(-n // k)

        def body(i, out):
            # the last chunk is clamped back to end at n: its overlap with
            # the one before recomputes the same labels from `q`
            start = jnp.minimum(i * size, n - size)
            part = _merge_join(lax.dynamic_slice_in_dim(q, start, size),
                               sorted_vals, t_delta)
            return lax.dynamic_update_slice_in_dim(out, part, start, 0)

        return lax.fori_loop(0, k, body, jnp.zeros_like(q))


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
