"""Mamba selective scan — Pallas TPU kernel.

TPU adaptation: instead of the GPU kernel's warp-parallel scan, we tile the
channel (d_inner) dimension across the grid — each grid cell owns a
(block_d × d_state) slab of SSM state resident in VMEM — and walk the
sequence in chunks as the innermost sequential grid dimension, carrying the
state slab across chunk steps in scratch. Per chunk the recurrence runs as
a `fori_loop` over time with all operands VMEM-resident (block_d is a
multiple of 128 to keep the VPU lanes full; d_state=16 rides the sublane
dim). This trades the GPU's intra-warp parallel prefix for TPU-friendly
long-vector elementwise work on the channel axis, which is where Mamba's
parallelism actually is (state update is elementwise over d_inner).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BD = 128
DEFAULT_CHUNK = 256


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, hout_ref,
            h_ref, *, chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)          # (chunk, bd)
    dt = dt_ref[0].astype(jnp.float32)        # (chunk, bd)
    bm = b_ref[0].astype(jnp.float32)         # (chunk, st)
    cm = c_ref[0].astype(jnp.float32)         # (chunk, st)
    a = a_ref[...].astype(jnp.float32)        # (bd, st)
    d = d_ref[...].astype(jnp.float32)        # (1, bd)

    def step(t, carry):
        h, ys = carry
        dt_t = dt[t][:, None]                  # (bd,1)
        dA = jnp.exp(dt_t * a)                 # (bd,st)
        dBx = dt_t * bm[t][None, :] * x[t][:, None]
        h = dA * h + dBx
        y_t = jnp.sum(h * cm[t][None, :], axis=1) + x[t] * d[0]
        return h, jax.lax.dynamic_update_slice(ys, y_t[None], (t, 0))

    h0 = h_ref[...]
    ys0 = jnp.zeros((chunk, x.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, chunk, step, (h0, ys0))
    h_ref[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        hout_ref[0] = h.astype(hout_ref.dtype)


def selective_scan_fwd(xc, dt, Bm, Cm, A, D, *, block_d=DEFAULT_BD,
                       chunk=DEFAULT_CHUNK, interpret=False):
    """xc,dt: (B,S,di); Bm,Cm: (B,S,st); A: (di,st); D: (di,).

    Returns (y: (B,S,di) f32, h_final: (B,di,st) f32).
    """
    B, S, di = xc.shape
    st = A.shape[-1]
    bd = min(block_d, di)
    ck = min(chunk, S)
    assert di % bd == 0 and S % ck == 0
    nd, nc = di // bd, S // ck
    d2 = D.reshape(1, di)

    y, h = pl.pallas_call(
        functools.partial(_kernel, chunk=ck, n_chunks=nc),
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, ck, bd), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, ck, st), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, ck, st), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((bd, st), lambda b, i, c: (i, 0)),
            pl.BlockSpec((1, bd), lambda b, i, c: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, bd, st), lambda b, i, c: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, st), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, st), jnp.float32)],
        interpret=interpret,
        name="ssm_selective_scan",
    )(xc, dt, Bm, Cm, A, d2)
    return y, h
