"""Beyond-paper: blockwise-quantized model averaging (wire emulation).

The paper explicitly notes it does NOT compress uploads ("we do not employ
the compression technique"); we add upload compression as a separately-
reported optimization. The wire format is blockwise quantization at
``bits ∈ {8, 4, 1}`` — symmetric absmax integer codes for 8/4 (int4
packed two per byte), sign + per-block mean-|x| scale for 1-bit — with
one f32 scale per block (``repro.kernels.quantize``). Two wire paths
implement the same format:

* **leafwise** (this module, the tested reference): every parameter leaf is
  independently quantize-roundtripped and the dequantized f32 tensors are
  averaged afterwards. Simple, but it costs two pallas launches + a
  host-shaped pad/reshape per leaf, leaves with ``size < block`` (or
  scalars) bypass the codec entirely and travel uncompressed —
  ``compressed_bytes`` accounts for that bypass at raw-dtype rates — and
  because the STACKED (K, ...) leaf is flattened as one array, a
  quantization block can straddle two participants' data mid-leaf (a
  physical wire could not do that; the flat-buffer path quantizes strict
  per-participant rows).
* **flat-buffer** (``repro.core.flatbuf`` + ``repro.kernels.comm``,
  selected by ``CoLearner(codec=FlatFusedIntN(...))`` or the legacy
  ``from_flags(compress="fused")``): the whole stacked tree is
  flattened into one contiguous ``(K, N_pad)`` f32 buffer and a single
  fused quantize->average->dequantize kernel performs Eq. 2 in one
  blockwise pass. No leaf escapes the wire format and
  ``flatbuf.wire_bytes`` is exact by construction.

Byte accounting bills the canonical encoded representation INCLUDING the
block padding a real wire would carry: a quantized leaf costs
``ceil(n/block)`` whole packed blocks plus one scale each
(``scale_bytes`` wide, f32 by default), parameterized over the payload
bit width — never hardcoded to 1 byte/element.

``quantize_roundtrip_ef`` adds error-feedback residual memory (the
standard trick that keeps int4 / 1-bit quantization convergent): each
participant quantizes ``x + e`` and keeps ``e' = (x + e) - dequant`` for
the next round; bypassed leaves carry a zero residual forever.

Reported ONLY in EXPERIMENTS.md §Perf beyond-paper rows, never mixed into
the paper-faithful baseline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops


def quantize_roundtrip(tree, block=256, impl=None, bits=8):
    """Simulate the compressed upload: quantize then dequantize every leaf.

    Leaves with fewer than ``block`` elements (and scalars) are returned
    unchanged — they go on the wire uncompressed (see ``compressed_bytes``).
    ``impl=None`` is the kernel on a TPU backend, the reference elsewhere.
    """
    impl = kops.resolve_impl(impl)

    def one(t):
        if t.ndim == 0 or t.size < block:
            return t
        q, scale, shape = kops.quantize_blockwise(t, block=block, bits=bits,
                                                  impl=impl)
        return kops.dequantize_blockwise(q, scale, shape, bits=bits,
                                         impl=impl).astype(t.dtype)
    return jax.tree.map(one, tree)


def quantize_roundtrip_ef(tree, residual, block=256, impl=None, bits=8):
    """Error-feedback leafwise roundtrip: quantize ``t + e`` per leaf and
    return ``(roundtripped tree, new residual tree)`` with
    ``e' = (t + e) - dequant``. Residual leaves are f32 mirrors of the
    params; bypassed leaves pass through unchanged with residual zero.
    """
    impl = kops.resolve_impl(impl)

    def one(t, e):
        if t.ndim == 0 or t.size < block:
            return t, e
        y = t.astype(jnp.float32) + e
        q, scale, shape = kops.quantize_blockwise(y, block=block, bits=bits,
                                                  impl=impl)
        dq = kops.dequantize_blockwise(q, scale, shape, bits=bits, impl=impl)
        return dq.astype(t.dtype), y - dq
    flat, treedef = jax.tree.flatten(tree)
    res_flat = jax.tree.leaves(residual)
    out = [one(t, e) for t, e in zip(flat, res_flat)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))


def make_compress_fn(block=256, impl=None, bits=8):
    """compress_fn for CoLearner: emulates the quantized wire format."""
    def fn(stacked):
        return quantize_roundtrip(stacked, block=block, impl=impl, bits=bits)
    return fn


def block_bytes(block, bits, scale_bytes=4):
    """Wire bytes of ONE encoded block: packed payload + its scale."""
    from repro.kernels.quantize import check_bits
    check_bits(bits)
    return block * bits // 8 + scale_bytes


def compressed_bytes(tree, block=256, bits=8, scale_bytes=4):
    """Per-participant wire bytes of the leafwise encoding.

    ``tree`` is ONE participant's (unstacked) params: each quantized leaf
    costs ``ceil(n/block)`` whole packed blocks (the encoder pads the last
    block — those bytes go on the wire) plus one ``scale_bytes`` scale per
    block; leaves below the block threshold bypass the codec and are
    counted at their raw dtype size — the same bypass rule
    ``quantize_roundtrip`` applies. Note the in-sim emulation runs the
    roundtrip on the STACKED tree, where the threshold sees K*size and
    blocks can straddle participants, so at small K its behavior can
    differ from this per-upload accounting (the flat-buffer path has no
    such gap — ``flat_compressed_bytes`` is exact)."""
    per_block = block_bytes(block, bits, scale_bytes)
    total = 0
    for t in jax.tree.leaves(tree):
        n = t.size
        if t.ndim == 0 or n < block:
            total += n * t.dtype.itemsize        # uploaded uncompressed
        else:
            total += (-(-n // block)) * per_block
    return total


def flat_compressed_bytes(tree, block=256, bits=8, scale_bytes=4):
    """Exact per-participant wire bytes of the flat-buffer codec for a
    STACKED tree (leading participant dim on every leaf) — every element,
    however small its leaf, is on the packed ``bits`` + scale format."""
    from repro.core import flatbuf
    return flatbuf.wire_bytes(flatbuf.make_layout(tree, block=block),
                              bits=bits, scale_bytes=scale_bytes)
