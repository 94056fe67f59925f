"""Path compression (pointer doubling) — the paper's core primitive.

Shared-memory Alg. 1 lines 9-19 (Maack et al. [33]) adapted to TPU:
per-thread active lists become whole-array functional gathers
`d_{t+1}[v] = d_t[d_t[v]]`; the while-loop convergence check replaces
active-list deletion.  Each round doubles every pointer-chain length, so a
chain of length L resolves in ceil(log2 L) rounds.  Entries < 0 are
"unmasked" sentinels (paper Alg. 3 line 12) and are left untouched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def jump(d: jax.Array) -> jax.Array:
    """One pointer-doubling round: d[v] <- d[d[v]], masked entries fixed."""
    nd = jnp.take(d, jnp.clip(d, 0), axis=0)
    return jnp.where(d >= 0, nd, d)


def path_compress(d: jax.Array, max_iter: int = 64):
    """Iterate pointer doubling to the fixpoint.

    Args:
      d: int array of pointers into itself (flat), -1 for unmasked entries.
      max_iter: safety bound; 64 covers any chain up to 2**64.

    Returns:
      (compressed pointers, number of rounds executed).
    """
    def cond(state):
        _, changed, i = state
        return changed & (i < max_iter)

    def body(state):
        cur, _, i = state
        nxt = jump(cur)
        return nxt, jnp.any(nxt != cur), i + jnp.int32(1)

    with jax.named_scope("dpc.doubling"):
        out, _, iters = lax.while_loop(
            cond, body, (d, jnp.asarray(True), jnp.int32(0))
        )
    return out, iters


def path_compress_unrolled(d: jax.Array, rounds: int) -> jax.Array:
    """Fixed number of doubling rounds (for kernels / known-diameter blocks)."""
    for _ in range(rounds):
        d = jump(d)
    return d


def is_converged(d: jax.Array) -> jax.Array:
    """True iff every masked pointer is a fixpoint (points at a root)."""
    return jnp.all(jump(d) == d)
