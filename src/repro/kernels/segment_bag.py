"""Pallas TPU kernel: fused EmbeddingBag (gather + segment-sum).

The recsys hot path (assignment §RecSys: "the embedding LOOKUP is the hot
path"; JAX has no native EmbeddingBag).  TPU adaptation: the table never
fits VMEM (10^6-10^9 rows), so instead of row-DMA chasing we tile the
VOCAB: grid = (vocab_tiles, batch_blocks); step (t, b) loads table tile t
(rows [t*Vb, (t+1)*Vb)) and the id block b into VMEM, accumulates the
partial bag sums for ids that fall inside the tile, and the sequential
vocab axis revisits the output block — one HBM pass over the table per
batch block, fully vectorised masking instead of scalar gathers.

This trades gather irregularity for a dense sweep: optimal when
batch * L >= vocab_tiles (training / bulk-serving shapes); ops.py keeps the
XLA gather path for the sparse-read regimes (serve_p99).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(ids_ref, table_ref, out_ref, *, vocab_block, n_tiles):
    t = pl.program_id(1)  # vocab tile — innermost (sequential on TPU), so
    #                       the revisited out block accumulates in VMEM
    ids = ids_ref[...]                 # (Bb, L) int32, -1 pads
    tile = table_ref[...]              # (Vb, D)
    lo = t * vocab_block
    local = ids - lo                   # (Bb, L)
    in_tile = (local >= 0) & (local < vocab_block)
    safe = jnp.clip(local, 0, vocab_block - 1)
    rows = jnp.take(tile, safe, axis=0).astype(jnp.float32)  # (Bb, L, D)
    rows = jnp.where(in_tile[..., None], rows, 0.0)
    partial = rows.sum(axis=1)                     # (Bb, D) f32 accumulate

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("vocab_block", "batch_block",
                                             "interpret"))
def segment_bag(table: jax.Array, ids: jax.Array, vocab_block: int = 2048,
                batch_block: int = 256, *, interpret: bool) -> jax.Array:
    """table: (V, D); ids: (B, L) int32 with -1 padding.  Returns (B, D)
    sum-bags in table.dtype (fp32 accumulation across vocab tiles).
    V % vocab_block == 0 or vocab_block clamped; same for B."""
    v, d = table.shape
    b, l = ids.shape
    if v % vocab_block:
        vocab_block = v
    if b % batch_block:
        batch_block = b
    n_tiles = v // vocab_block
    grid = (b // batch_block, n_tiles)
    kernel = functools.partial(_kernel, vocab_block=vocab_block,
                               n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((batch_block, l), lambda i, t: (i, 0)),
            pl.BlockSpec((vocab_block, d), lambda i, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((batch_block, d), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        interpret=interpret,
    )(ids, table).astype(table.dtype)
