"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# --- steepest_neighbor ------------------------------------------------------


def steepest_neighbor_ref(order: jax.Array, offsets, id_offset: int = 0):
    """Reference for the 3D steepest-neighbor stencil: for every voxel the
    global flat id of the argmax-order vertex among itself and `offsets`.
    order: (X, Y, Z) int32; returns (X, Y, Z) int32 of flat ids + id_offset.
    """
    from repro.core.steepest import shift_fill
    n = order.size
    idx = (jnp.arange(n, dtype=jnp.int32) + id_offset).reshape(order.shape)
    best_val, best_idx = order, idx
    fill = jnp.iinfo(order.dtype).min
    for off in offsets:
        cv = shift_fill(order, off, fill)
        ci = shift_fill(idx, off, -1)
        better = cv > best_val
        best_val = jnp.where(better, cv, best_val)
        best_idx = jnp.where(better, ci, best_idx)
    return best_idx


# --- block_pathcompress -----------------------------------------------------


def block_pathcompress_ref(d: jax.Array, rounds: int, base: int = 0):
    """`rounds` pointer-doubling steps where gathers are confined to the
    block: out-of-block or negative pointers are fixed points."""
    n = d.shape[0]
    for _ in range(rounds):
        local = d - base
        in_block = (d >= 0) & (local >= 0) & (local < n)
        nd = d[jnp.clip(local, 0, n - 1)]
        d = jnp.where(in_block, nd, d)
    return d


# --- fused_local_phase -------------------------------------------------------


def fused_local_phase_ref(field, connectivity: int, mode: str = "manifold",
                          ghost_axes: tuple = (), id_dtype=None):
    """Bit-exact host-side oracle for the fused pointer-init kernel: the
    plain init (steepest argmax / largest masked neighbor id) with the first
    and last layer along each of `ghost_axes` forced to self-pointers (cc:
    only where masked).  Returns the (X, Y, Z) pointer array."""
    from repro.core.steepest import grid_steepest, grid_mask_argmax
    field = np.asarray(field)
    n = field.size
    if id_dtype is None:
        id_dtype = jnp.int32 if n < 2**31 else jnp.int64
    np_dt = np.dtype(id_dtype)
    if mode == "manifold":
        d = np.asarray(grid_steepest(jnp.asarray(field), connectivity))
    else:
        d = np.asarray(grid_mask_argmax(jnp.asarray(field), connectivity))
    d = d.astype(np_dt)
    keep = np.zeros(field.shape, bool)
    for a in ghost_axes:
        idx = [slice(None)] * field.ndim
        idx[a] = 0
        keep[tuple(idx)] = True
        idx[a] = field.shape[a] - 1
        keep[tuple(idx)] = True
    keep = keep.ravel()
    if mode == "cc":
        keep = keep & (field.ravel() != 0)
    d = np.where(keep, np.arange(n, dtype=np_dt), d)
    return jnp.asarray(d.reshape(field.shape))


# --- flash attention ---------------------------------------------------------


def mha_ref(q, k, v, causal: bool = False, scale: float | None = None):
    """Unfused reference attention.  q: (B, H, Sq, D), k/v: (B, Hkv, Skv, D).
    GQA: H a multiple of Hkv."""
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    scale = scale or (1.0 / np.sqrt(d))
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    if causal:
        skv = k.shape[2]
        mask = jnp.arange(sq)[:, None] + (skv - sq) >= jnp.arange(skv)[None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vv.astype(jnp.float32)).astype(q.dtype)


def flash_attention_ref(q, k, v, causal: bool = False, block_kv: int = 128,
                        scale: float | None = None):
    """Chunked (online-softmax) attention in pure jnp — numerically the
    flash schedule, used both as the kernel oracle and as the model-side
    attention implementation for dry-runs (no S x S buffer)."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = h // hkv
    scale = scale or (1.0 / np.sqrt(d))
    qf = q.astype(jnp.float32) * scale
    nblk = max(skv // block_kv, 1)
    blk = skv // nblk

    def body(carry, i):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(k, i * blk, blk, axis=2)
        vs = lax.dynamic_slice_in_dim(v, i * blk, blk, axis=2)
        ks = jnp.repeat(ks, group, axis=1).astype(jnp.float32)
        vs = jnp.repeat(vs, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, ks)
        if causal:
            qpos = jnp.arange(sq)[:, None] + (skv - sq)
            kpos = i * blk + jnp.arange(blk)[None, :]
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vs)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nblk))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
