"""Fused quantize->average->dequantize — Pallas TPU kernel (Eq. 2 wire path).

One blockwise pass over the flat-buffer wire codec's ``(K, N_pad)`` stacked
participant buffer (``repro.core.flatbuf``): each grid step loads one
``(K, ROWS, block)`` tile into VMEM, quantizes every participant row with
one f32 scale per (participant, row) — the same wire format as
``repro.kernels.quantize``, generalized over ``bits ∈ {8, 4, 1}`` — widens
the codes through int32, scales them back to the exactly-dequantized f32
payloads (|q| <= 127, so each ``q * scale`` product is exact in f32), and
reduces them to the Eq. 2 mean in one shot. This replaces the leafwise
path's ~2 pallas_call launches + host-side pad/reshape per parameter leaf
plus a separate whole-tree mean with a single kernel over a single buffer.

Scales are per participant (each participant quantizes its own upload
before it ever sees the others), so the cross-participant accumulation
happens on the dequantized payloads, not in the shared-integer domain.

``quant_avg_dequant_ef_fwd`` is the error-feedback variant: the quantizer
input is ``x + e`` (the row plus its residual memory), and the kernel emits
BOTH the Eq. 2 mean of the dequantized payloads and the new residual
``e' = (x + e) - dequant(quant(x + e))`` — the standard trick that keeps
aggressive (int4 / 1-bit) quantization convergent — still in one pass over
one buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# one source of truth for the wire tile shape: the quantize kernel owns it
# (flatbuf layouts and this kernel's grid must stay in lockstep with it)
from repro.kernels.quantize import DEFAULT_BLOCK, QMAX, ROWS, check_bits


def _quant_dequant_tile(x, bits):
    """(K, ROWS, block) f32 -> dequantized wire roundtrip, same shape."""
    if bits == 1:
        scale = jnp.mean(jnp.abs(x), axis=2, keepdims=True)   # (K, ROWS, 1)
        q = jnp.where(x > 0, 1, -1).astype(jnp.int8)
    else:
        qmax = QMAX[bits]
        amax = jnp.max(jnp.abs(x), axis=2, keepdims=True)     # (K, ROWS, 1)
        scale = jnp.where(amax > 0, amax / qmax, 1.0)
        q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    return q.astype(jnp.int32).astype(jnp.float32) * scale


def _qad_kernel(x_ref, o_ref, *, k, bits):
    x = x_ref[...]                                      # (K, ROWS, block) f32
    dq = _quant_dequant_tile(x, bits)
    o_ref[...] = jnp.sum(dq, axis=0) / k                # Eq. 2 over K


def _qad_ef_kernel(x_ref, e_ref, o_ref, ne_ref, *, k, bits):
    y = x_ref[...] + e_ref[...]                         # (K, ROWS, block) f32
    dq = _quant_dequant_tile(y, bits)
    o_ref[...] = jnp.sum(dq, axis=0) / k
    ne_ref[...] = y - dq                                # residual memory


def _pad_tiles(buf, block):
    """Pad a (K, n) buffer to whole (ROWS, block) tiles -> (K, nb, block)."""
    K, n = buf.shape
    tile = ROWS * block
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        buf = jnp.pad(buf, ((0, 0), (0, n_pad - n)))
    return buf.reshape(K, n_pad // block, block), n_pad


def quant_avg_dequant_fwd(buf, *, block=DEFAULT_BLOCK, bits=8,
                          interpret=False):
    """buf: (K, n) f32 -> (n,) f32 mean of the wire-roundtripped rows.

    ``n`` is padded up to whole ``(ROWS, block)`` tiles internally (the flat
    codec's ``N_pad`` already is, so the pad is a no-op on the hot path);
    zero pad quantizes and dequantizes to exactly zero.
    """
    check_bits(bits)
    K, n = buf.shape
    xb, n_pad = _pad_tiles(buf, block)
    nb = n_pad // block
    out = pl.pallas_call(
        functools.partial(_qad_kernel, k=K, bits=bits),
        grid=(nb // ROWS,),
        in_specs=[pl.BlockSpec((K, ROWS, block), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=interpret,
        name="eq2_flat_mean",
    )(xb)
    return out.reshape(n_pad)[:n]


def quant_avg_dequant_ef_fwd(buf, residual, *, block=DEFAULT_BLOCK, bits=8,
                             interpret=False):
    """Error-feedback fused pass: (buf, residual) both (K, n) f32 ->
    ((n,) f32 mean of the roundtripped ``buf + residual`` rows,
    (K, n) f32 new residual). Zero pad stays exactly zero in both outputs.
    """
    check_bits(bits)
    if residual.shape != buf.shape:
        raise ValueError(f"residual shape {residual.shape} != buf shape "
                         f"{buf.shape}")
    K, n = buf.shape
    xb, n_pad = _pad_tiles(buf, block)
    eb, _ = _pad_tiles(residual, block)
    nb = n_pad // block
    out, ne = pl.pallas_call(
        functools.partial(_qad_ef_kernel, k=K, bits=bits),
        grid=(nb // ROWS,),
        in_specs=[pl.BlockSpec((K, ROWS, block), lambda i: (0, i, 0)),
                  pl.BlockSpec((K, ROWS, block), lambda i: (0, i, 0))],
        out_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
                   pl.BlockSpec((K, ROWS, block), lambda i: (0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.float32),
                   jax.ShapeDtypeStruct((K, nb, block), jnp.float32)],
        interpret=interpret,
        name="eq2_flat_mean_ef",
    )(xb, eb)
    return out.reshape(n_pad)[:n], ne.reshape(K, n_pad)[:, :n]
