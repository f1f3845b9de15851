"""Blockwise sub-f32 quant/dequant — Pallas TPU kernel (comm compression).

Used by the beyond-paper compressed model-averaging path: parameters are
flattened, padded, and quantized in VMEM-resident tiles of (rows × block)
with one f32 scale per block row. Tiles are (8, 256) by default —
8 sublanes × 256 lanes (two 128-lane vregs), a natural VPU shape.

The wire supports ``bits ∈ {8, 4, 1}``:

* 8 / 4 — symmetric absmax quantization to ``qmax = 2**(bits-1) - 1``
  integer codes (127 / 7); int4 codes are packed two per byte.
* 1 — sign quantization: codes are ±1 packed eight per byte, and the
  per-block scale is ``mean(|x|)`` (the L2-optimal magnitude for a sign
  code, as in 1-bit SGD / signSGD-with-majority); an all-zero block gets
  scale 0 so it dequantizes to exactly 0, preserving the flat-buffer
  zero-padding contract.

Bit-packing is plain jnp OUTSIDE the Pallas kernels (``pack_codes`` /
``unpack_codes``), shared by the ref oracles so both impls produce the
identical packed wire payload; the kernels always see unpacked int8 codes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 256
ROWS = 8

# symmetric-integer code range per bit width (1-bit is sign-coded, not here)
QMAX = {8: 127.0, 4: 7.0}


def check_bits(bits):
    if bits not in (8, 4, 1):
        raise ValueError(f"bits must be 8, 4, or 1; got {bits}")


def pack_codes(q, bits):
    """(nb, block) int8 codes -> (nb, block*bits//8) packed payload.

    bits=8 is the identity; bits=4 packs two's-complement nibbles (even
    index = low nibble); bits=1 packs eight sign bits per byte (LSB =
    lowest index, set bit = +1).
    """
    check_bits(bits)
    if bits == 8:
        return q
    if bits == 4:
        u = q.astype(jnp.uint8) & 0xF
        return (u[:, 0::2] | (u[:, 1::2] << 4)).astype(jnp.uint8)
    b = (q > 0).astype(jnp.uint8).reshape(q.shape[0], -1, 8)
    w = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return (b * w).sum(axis=2).astype(jnp.uint8)


def unpack_codes(p, bits):
    """Exact inverse of ``pack_codes``: packed payload -> int8 codes."""
    check_bits(bits)
    if bits == 8:
        return p
    if bits == 4:
        u = jnp.stack([p & 0xF, p >> 4], axis=-1).reshape(p.shape[0], -1)
        s = u.astype(jnp.int8)
        return jnp.where(s > 7, s - 16, s)
    w = jnp.arange(8, dtype=jnp.uint8)
    b = (p[:, :, None] >> w) & 1
    return jnp.where(b == 1, 1, -1).astype(jnp.int8).reshape(p.shape[0], -1)


def packed_width(block, bits):
    """Payload columns of one packed block row."""
    check_bits(bits)
    return block * bits // 8


def _q_kernel(x_ref, q_ref, s_ref, *, qmax):
    x = x_ref[...].astype(jnp.float32)                 # (ROWS, block)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # (ROWS, 1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    s_ref[...] = scale


def _q1_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                 # (ROWS, block)
    # mean |x| minimizes ||x - scale*sign(x)||_2; zero block -> scale 0
    s_ref[...] = jnp.mean(jnp.abs(x), axis=1, keepdims=True)
    q_ref[...] = jnp.where(x > 0, 1, -1).astype(jnp.int8)


def _dq_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def quantize_blockwise_fwd(x, *, block=DEFAULT_BLOCK, bits=8,
                           interpret=False):
    """x: any shape -> (q packed (nblocks, block*bits//8), scale f32
    (nblocks,), shape)."""
    check_bits(bits)
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    nb = -(-n // block)
    nb = -(-nb // ROWS) * ROWS                          # pad rows to ROWS
    flat = jnp.pad(flat, (0, nb * block - n))
    xb = flat.reshape(nb, block)
    kernel = (_q1_kernel if bits == 1
              else functools.partial(_q_kernel, qmax=QMAX[bits]))
    q, s = pl.pallas_call(
        kernel,
        grid=(nb // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
                   pl.BlockSpec((ROWS, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret,
        name="eq2_quantize_blockwise",
    )(xb)
    return pack_codes(q, bits), s[:, 0], x.shape


def dequantize_blockwise_fwd(q, scale, shape, *, bits=8, interpret=False):
    check_bits(bits)
    q = unpack_codes(q, bits)
    nb, block = q.shape
    if scale.shape != (nb,):
        raise ValueError(f"scale shape {scale.shape} != ({nb},)")
    n = 1
    for s in shape:
        n *= s
    if n > nb * block:
        raise ValueError(f"shape {shape} needs {n} elements; payload has "
                         f"only {nb}x{block}")
    # the quantizer pads its row count to ROWS, but accept any nb: a grid of
    # nb // ROWS would silently drop the trailing nb % ROWS rows
    nb_pad = -(-nb // ROWS) * ROWS
    if nb_pad != nb:
        q = jnp.pad(q, ((0, nb_pad - nb), (0, 0)))
        scale = jnp.pad(scale, (0, nb_pad - nb))
    x = pl.pallas_call(
        _dq_kernel,
        grid=(nb_pad // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
                  pl.BlockSpec((ROWS, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb_pad, block), jnp.float32),
        interpret=interpret,
        name="eq2_dequantize_blockwise",
    )(q, scale[:, None])
    return x.reshape(-1)[:n].reshape(shape)
