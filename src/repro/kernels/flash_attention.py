"""Flash attention forward — Pallas TPU kernel.

TPU adaptation of the flash algorithm: the KV stream is the innermost
(sequential) grid dimension; the running (m, l, acc) online-softmax state
lives in VMEM scratch across KV steps; Q/K/V tiles are BlockSpec'd into
VMEM with MXU-aligned shapes (q block 256×hd, kv block 512×hd, hd a
multiple of 128 for full MXU occupancy at 128-lane width).

Supports causal masking, sliding window, and GQA (KV head index derived
from the Q head index in the BlockSpec index maps — no KV replication in
HBM or VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 256
DEFAULT_BK = 512


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, window, bq, bk, n_kv_blocks, seq_k, seq_q):
    """Grid: (B, H, nq, nk); innermost nk is sequential on TPU."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd_v)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (seq_k - seq_q)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos <= q_pos
    if window:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, n_kv_heads, window=0, softmax_scale=None,
                        block_q=DEFAULT_BQ, block_k=DEFAULT_BK,
                        interpret=False):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd/hd_v) -> (B,Sq,H,hd_v). Causal."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    hd_v = v.shape[-1]
    KV = n_kv_heads
    G = H // KV
    scale = softmax_scale or hd ** -0.5
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    nq, nk = Sq // bq, Sk // bk
    assert Sq % bq == 0 and Sk % bk == 0

    # (B,H,S,hd) layouts so the head dim is a leading grid dim
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, bq=bq, bk=bk,
        n_kv_blocks=nk, seq_k=Sk, seq_q=Sq)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd_v), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd_v), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max m
            pltpu.VMEM((bq, 1), jnp.float32),      # running denom l
            pltpu.VMEM((bq, hd_v), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="attention_flash",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
