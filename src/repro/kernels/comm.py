"""Fused quantize->average->dequantize — Pallas TPU kernel (Eq. 2 wire path).

One blockwise pass over the flat-buffer wire codec's ``(K, N_pad)`` stacked
participant buffer (``repro.core.flatbuf``), viewed as ``(K, nb, block)``
block rows. Every ``(participant, block row)`` is quantized with one f32
scale — the same wire format and the same ``ROWS x block`` granule as
``repro.kernels.quantize``, generalized over ``bits ∈ {8, 4, 1}`` — its
codes widened through int32 and scaled back to the exactly-dequantized f32
payloads (|q| <= 127, so each ``q * scale`` product is exact in f32), and
the K payloads reduced to the Eq. 2 mean in one shot. This replaces the
leafwise path's ~2 pallas_call launches + host-side pad/reshape per
parameter leaf plus a separate whole-tree mean with a single kernel over a
single buffer.

Tiling: a grid step moves one ``(K, R, block)`` tile, with R in the
hundreds of block rows (``tile_rows``: about 1 MiB of K-row blocks a step,
so the double-buffered blocks take a few MiB of VMEM). The bytes, not the
fixed cost of a grid step, then set the kernel's time: 8-row steps spent
most of theirs in that fixed cost. Block rows are independent (one scale
each, the mean runs over K), so the tile size changes no number. The grid
is ``cdiv(nb, R)``; nb need only be a multiple of ``ROWS``, so the last
tile may be partial: its rows past the end are never read back, and their
writes are dropped.

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

# one source of truth for the wire granule: the quantize kernel owns it.
# flatbuf's layout must stay in lockstep with it (whole ROWS x block
# tiles); this kernel's grid steps need only be a multiple of ROWS rows.
from repro.kernels.quantize import DEFAULT_BLOCK, QMAX, ROWS, check_bits

#: bytes of the K-row blocks one grid step moves (before double buffering)
TILE_BYTES = 1 << 20


def tile_rows(k, block, nb, k_row_operands=1):
    """Block rows R of one grid step: ``k_row_operands`` (K, R, block) f32
    blocks fill ``TILE_BYTES``, rounded down to a multiple of ROWS, and
    never more than the buffer's ``nb`` rows."""
    rows = TILE_BYTES // (k_row_operands * k * block * 4) // ROWS * ROWS
    return min(max(rows, ROWS), nb)


def _quant_dequant_tile(x, bits):
    """(K, R, block) f32 -> dequantized wire roundtrip, same shape."""
    if bits == 1:
        scale = jnp.mean(jnp.abs(x), axis=2, keepdims=True)   # (K, R, 1)
        q = jnp.where(x > 0, 1, -1).astype(jnp.int8)
    else:
        qmax = QMAX[bits]
        amax = jnp.max(jnp.abs(x), axis=2, keepdims=True)     # (K, R, 1)
        scale = jnp.where(amax > 0, amax / qmax, 1.0)
        q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    return q.astype(jnp.int32).astype(jnp.float32) * scale


def _qad_kernel(x_ref, o_ref, *, k, bits):
    x = x_ref[...]                                      # (K, R, block) f32
    dq = _quant_dequant_tile(x, bits)
    o_ref[...] = jnp.sum(dq, axis=0) / k                # Eq. 2 over K


def _qad_ef_kernel(x_ref, e_ref, o_ref, ne_ref, *, k, bits):
    y = x_ref[...] + e_ref[...]                         # (K, R, block) f32
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
    zero pad quantizes and dequantizes to exactly zero. The grid walks the
    ``nb`` block rows ``tile_rows(K, block, nb)`` at a time, a partial tile
    last where that R does not divide nb.
    """
    check_bits(bits)
    K, n = buf.shape
    xb, n_pad = _pad_tiles(buf, block)
    nb = n_pad // block
    r = tile_rows(K, block, nb)
    out = pl.pallas_call(
        functools.partial(_qad_kernel, k=K, bits=bits),
        grid=(pl.cdiv(nb, r),),
        in_specs=[pl.BlockSpec((K, r, block), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((r, block), lambda i: (i, 0)),
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
    Tiled as ``quant_avg_dequant_fwd``, with R sized for the three K-row
    blocks a step moves (buffer, residual, new residual).
    """
    check_bits(bits)
    if residual.shape != buf.shape:
        raise ValueError(f"residual shape {residual.shape} != buf shape "
                         f"{buf.shape}")
    K, n = buf.shape
    xb, n_pad = _pad_tiles(buf, block)
    eb, _ = _pad_tiles(residual, block)
    nb = n_pad // block
    r = tile_rows(K, block, nb, k_row_operands=3)
    k_rows = pl.BlockSpec((K, r, block), lambda i: (0, i, 0))
    out, ne = pl.pallas_call(
        functools.partial(_qad_ef_kernel, k=K, bits=bits),
        grid=(pl.cdiv(nb, r),),
        in_specs=[k_rows, k_rows],
        out_specs=[pl.BlockSpec((r, block), lambda i: (i, 0)), k_rows],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.float32),
                   jax.ShapeDtypeStruct((K, nb, block), jnp.float32)],
        interpret=interpret,
        name="eq2_flat_mean_ef",
    )(xb, eb)
    return out.reshape(n_pad)[:n], ne.reshape(K, n_pad)[:, :n]
