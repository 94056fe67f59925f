"""Pallas TPU kernel: fused flash attention (fwd), GQA-aware.

The LM substrate's compute hot spot (prefill_32k would otherwise materialise
S x S scores).  Canonical TPU schedule: grid (batch*heads, nQ, nK) with the
kv axis innermost (sequential on TPU), online-softmax state (m, l, acc) in
VMEM scratch carried across kv steps, finalised on the last kv block.
MXU-aligned block sizes (multiples of 128 on the contracted dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, block_q, block_k, seq_q, seq_k):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale        # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                # (BK, D)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (BQ, BK)

    if causal:
        iq = pl.program_id(1)
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) + (seq_k - seq_q)
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 128,
                    block_k: int = 128, *, interpret: bool):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Skv, D) with H % Hkv == 0.

    Returns (B, H, Sq, D).  GQA is handled by an index-map trick: kv blocks
    for query head h come from kv head h // group — no jnp.repeat copy.
    """
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = h // hkv
    scale = 1.0 / np.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * hkv, sk, d)
    vr = v.reshape(b * hkv, sk, d)

    grid = (b * h, sq // block_q, sk // block_k)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=sq, seq_k=sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, iq, ik: (bh // group, ik, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, iq, ik: (bh // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)
