"""mLSTM (matrix-memory LSTM, xLSTM) — Pallas TPU kernel.

TPU adaptation: each grid cell owns one (batch, head); the (hd × hd) matrix
memory C, normalizer n and stabilizer m live in VMEM scratch and persist
across sequence chunks (innermost sequential grid dim). Within a chunk the
stabilized recurrence runs as a `fori_loop`; the rank-1 update v·kᵀ and the
readout C·q map onto the MXU as (hd×1)·(1×hd) and (hd×hd)·(hd×1) dots with
hd a multiple of 128. This is the TPU-idiomatic replacement for the GPU
version's shared-memory tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _kernel(q_ref, k_ref, v_ref, i_ref, f_ref, h_ref,
            c_s, n_s, m_s, *, chunk, n_chunks, hd):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_s[...] = jnp.zeros_like(c_s)
        n_s[...] = jnp.zeros_like(n_s)
        m_s[...] = jnp.full_like(m_s, -1e30)

    q = q_ref[0, 0].astype(jnp.float32) * hd ** -0.25   # (chunk, hd)
    k = k_ref[0, 0].astype(jnp.float32) * hd ** -0.25
    v = v_ref[0, 0].astype(jnp.float32)
    ig = i_ref[0, 0].astype(jnp.float32)                # (chunk, 1)
    fg = f_ref[0, 0].astype(jnp.float32)
    logf = -jnp.logaddexp(0.0, -fg)                     # log sigmoid

    def step(t, carry):
        C, n, m, hs = carry
        m_new = jnp.maximum(logf[t, 0] + m, ig[t, 0])
        i_p = jnp.exp(ig[t, 0] - m_new)
        f_p = jnp.exp(logf[t, 0] + m - m_new)
        C = f_p * C + i_p * jax.lax.dot(v[t][:, None], k[t][None, :])
        n = f_p * n + i_p * k[t][None, :]               # (1, hd)
        num = jax.lax.dot(C, q[t][:, None])[:, 0]       # (hd,)
        den = jnp.maximum(jnp.abs(jnp.sum(n[0] * q[t])), jnp.exp(-m_new))
        hs = jax.lax.dynamic_update_slice(hs, (num / den)[None], (t, 0))
        return C, n, m_new, hs

    hs0 = jnp.zeros((chunk, hd), jnp.float32)
    C, n, m, hs = jax.lax.fori_loop(
        0, chunk, step, (c_s[...], n_s[0:1], m_s[0, 0], hs0))
    c_s[...] = C
    n_s[...] = jnp.broadcast_to(n, n_s.shape)
    m_s[...] = jnp.full_like(m_s, m)
    h_ref[0, 0] = hs.astype(h_ref.dtype)


def mlstm_fwd(q, k, v, ig, fg, *, chunk=DEFAULT_CHUNK, interpret=False):
    """q,k,v: (B,S,H,hd); ig,fg: (B,S,H) raw gates -> h: (B,S,H,hd) f32.

    Note: the kernel applies the same 1/hd^(1/4) q,k scaling as the ref.
    """
    B, S, H, hd = q.shape
    ck = min(chunk, S)
    assert S % ck == 0
    nc = S // ck
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    it = ig.transpose(0, 2, 1)[..., None]
    ft = fg.transpose(0, 2, 1)[..., None]

    h = pl.pallas_call(
        functools.partial(_kernel, chunk=ck, n_chunks=nc, hd=hd),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, ck, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ck, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ck, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ck, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ck, 1), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, ck, hd), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((hd, hd), jnp.float32),   # matrix memory C
            pltpu.VMEM((1, hd), jnp.float32),    # normalizer n
            pltpu.VMEM((1, 1), jnp.float32),     # stabilizer m
        ],
        interpret=interpret,
        name="mlstm_chunk_scan",
    )(qt, kt, vt, it, ft)
    return h.transpose(0, 2, 1, 3)
