"""Jit'd dispatch wrappers for the Pallas kernels.

impl semantics:
  * "ref"     — pure-jnp oracle: the CPU path and the test oracle (also
                what the dry-run lowers, since Mosaic custom-calls need a
                TPU backend);
  * "pallas"  — the compiled TPU kernel, always: on a backend that cannot
                compile it the call fails instead of running something else;
  * "interpret" — the kernel body in Pallas interpret mode (bit-accurate
                kernel-body execution on the CPU — how tests validate the
                kernels here).

Callers that take ``impl=None`` resolve it with ``resolve_impl``: the
kernel on a TPU backend, the reference elsewhere.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import comm as _comm
from repro.kernels import flash_attention as _fa
from repro.kernels import mlstm as _ml
from repro.kernels import quantize as _qz
from repro.kernels import ref as _ref
from repro.kernels import selective_scan as _ss


def resolve_impl(impl=None):
    """``impl`` as given; None is "pallas" on a TPU backend, else "ref"."""
    if impl is not None:
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _interp(impl):
    return impl == "interpret"


def flash_attention(q, k, v, *, n_kv_heads, window=0, softmax_scale=None,
                    impl="pallas", **kw):
    if impl == "ref":
        return _ref.flash_attention_ref(q, k, v, n_kv_heads=n_kv_heads,
                                        window=window,
                                        softmax_scale=softmax_scale)
    return _fa.flash_attention_fwd(q, k, v, n_kv_heads=n_kv_heads,
                                   window=window, softmax_scale=softmax_scale,
                                   interpret=_interp(impl), **kw)


def selective_scan(xc, dt, Bm, Cm, A, D, *, impl="pallas", **kw):
    if impl == "ref":
        return _ref.selective_scan_ref(xc, dt, Bm, Cm, A, D)
    return _ss.selective_scan_fwd(xc, dt, Bm, Cm, A, D,
                                  interpret=_interp(impl), **kw)


def mlstm(q, k, v, ig, fg, *, impl="pallas", **kw):
    if impl == "ref":
        return _ref.mlstm_ref(q, k, v, ig, fg)
    h = _ml.mlstm_fwd(q, k, v, ig, fg, interpret=_interp(impl), **kw)
    return h, None


def quantize_blockwise(x, *, block=256, bits=8, impl="pallas", **kw):
    if impl == "ref":
        return _ref.quantize_blockwise_ref(x, block=block, bits=bits)
    return _qz.quantize_blockwise_fwd(x, block=block, bits=bits,
                                      interpret=_interp(impl), **kw)


def dequantize_blockwise(q, scale, shape, *, bits=8, impl="pallas", **kw):
    if impl == "ref":
        return _ref.dequantize_blockwise_ref(q, scale, shape, bits=bits)
    return _qz.dequantize_blockwise_fwd(q, scale, shape, bits=bits,
                                        interpret=_interp(impl), **kw)


def quant_avg_dequant(buf, *, block=256, bits=8, impl="pallas", **kw):
    """Fused Eq. 2 wire pass over a (K, n) flat buffer: quantize every
    participant row blockwise at ``bits``, dequantize, mean -> (n,) f32."""
    if impl == "ref":
        return _ref.quant_avg_dequant_ref(buf, block=block, bits=bits)
    return _comm.quant_avg_dequant_fwd(buf, block=block, bits=bits,
                                       interpret=_interp(impl), **kw)


def quant_avg_dequant_ef(buf, residual, *, block=256, bits=8, impl="pallas",
                         **kw):
    """Error-feedback fused Eq. 2 wire pass: quantize ``buf + residual``
    per participant row, return ((n,) mean, (K, n) new residual)."""
    if impl == "ref":
        return _ref.quant_avg_dequant_ef_ref(buf, residual, block=block,
                                             bits=bits)
    return _comm.quant_avg_dequant_ef_fwd(buf, residual, block=block,
                                          bits=bits, interpret=_interp(impl),
                                          **kw)
