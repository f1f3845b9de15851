"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Every kernel is exercised over a grid of shapes and dtypes and must
allclose the ref.py oracle (deliverable c).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 32),      # MHA
    (2, 256, 8, 2, 64),      # GQA
    (1, 128, 4, 1, 32),      # MQA
    (2, 512, 4, 2, 128),     # longer, MXU-width head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    o_ref = ref.flash_attention_ref(q, k, v, n_kv_heads=KV)
    o_pal = ops.flash_attention(q, k, v, n_kv_heads=KV, impl="interpret",
                                block_q=64, block_k=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    B, S, H, KV, hd = 1, 256, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    o_ref = ref.flash_attention_ref(q, k, v, n_kv_heads=KV, window=window)
    o_pal = ops.flash_attention(q, k, v, n_kv_heads=KV, window=window,
                                impl="interpret", block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_model_chunked_path():
    """The model's jnp chunked attention == the kernel (same contract)."""
    from repro.models.attention import chunked_attention
    B, S, H, KV, hd = 2, 256, 8, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    o_model = chunked_attention(q, k, v, n_kv_heads=KV, chunk_q=64,
                                chunk_kv=64)
    o_pal = ops.flash_attention(q, k, v, n_kv_heads=KV, impl="interpret",
                                block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_model),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,di,st,bd,ck", [
    (1, 64, 128, 8, 128, 32),
    (2, 128, 256, 16, 128, 64),
    (1, 256, 128, 4, 64, 256),
])
def test_selective_scan_sweep(B, S, di, st, bd, ck):
    ks = jax.random.split(KEY, 5)
    xc = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))) * 0.1
    Bm = jax.random.normal(ks[2], (B, S, st))
    Cm = jax.random.normal(ks[3], (B, S, st))
    A = -jnp.exp(jax.random.normal(ks[4], (di, st)) * 0.3)
    D = jnp.ones(di)
    y_ref, h_ref = ref.selective_scan_ref(xc, dt, Bm, Cm, A, D)
    y_pal, h_pal = ops.selective_scan(xc, dt, Bm, Cm, A, D, impl="interpret",
                                      block_d=bd, chunk=ck)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,hd,ck", [
    (1, 64, 2, 32, 32),
    (2, 128, 4, 64, 64),
])
def test_mlstm_sweep(B, S, H, hd, ck):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 2.0
    h_ref, _ = ref.mlstm_ref(q, k, v, ig, fg)
    h_pal, _ = ops.mlstm(q, k, v, ig, fg, impl="interpret", chunk=ck)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1000, 37), (256,), (8, 8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_roundtrip_and_match(shape, dtype):
    x = (jax.random.normal(KEY, shape) * 5).astype(dtype)
    q_p, s_p, shp = ops.quantize_blockwise(x, impl="interpret")
    q_r, s_r, _ = ref.quantize_blockwise_ref(x)
    # reduction-order ULP differences in the per-block scale may flip a
    # value sitting exactly on a quantization boundary by one step
    dq = np.abs(np.asarray(q_p[:q_r.shape[0]], np.int32)
                - np.asarray(q_r, np.int32))
    assert dq.max() <= 1 and (dq > 0).mean() < 1e-3
    x_back = ops.dequantize_blockwise(q_p, s_p, shp, impl="interpret")
    assert x_back.shape == shape
    scale = float(jnp.abs(x.astype(jnp.float32)).max())
    err = float(jnp.abs(x.astype(jnp.float32) - x_back).max())
    assert err <= scale / 127.0 + 1e-6   # int8 quantization bound


def test_dequantize_handles_row_counts_not_multiple_of_rows():
    """Regression: the dequantizer grid used to silently drop trailing rows
    when nb % ROWS != 0 (the ref quantizer pads only to whole blocks)."""
    from repro.kernels.quantize import ROWS
    n = 3 * 256                                   # nb=3, not a ROWS multiple
    x = jax.random.normal(KEY, (n,)) * 4
    q_r, s_r, shp = ref.quantize_blockwise_ref(x)
    assert q_r.shape[0] % ROWS != 0
    x_ref = ref.dequantize_blockwise_ref(q_r, s_r, shp)
    x_pal = ops.dequantize_blockwise(q_r, s_r, shp, impl="interpret")
    np.testing.assert_array_equal(np.asarray(x_pal), np.asarray(x_ref))


def test_dequantize_rejects_inconsistent_payload():
    q = jnp.zeros((2, 256), jnp.int8)
    with pytest.raises(ValueError):
        ops.dequantize_blockwise(q, jnp.ones((3,)), (2, 256),
                                 impl="interpret")
    with pytest.raises(ValueError):
        ops.dequantize_blockwise(q, jnp.ones((2,)), (10, 256),
                                 impl="interpret")


# ---------------------------------------------------------------------------
# fused quantize->average->dequantize (Eq. 2 wire pass)
# ---------------------------------------------------------------------------
#: buffers that span several of the kernel's grid tiles (``comm.tile_rows``)
#: with a partial last tile, for the plain and the EF pass alike: nb = 2R + 40
#: block rows for the plain pass's R at that K, less 300 elements (ragged n)
FAT = [(2, 1064 * 256 - 300), (3, 712 * 256 - 300), (4, 552 * 256 - 300)]
#: the zero tail of a FAT buffer, as the flat buffer's pad is: the ragged
#: last block row and two whole ones, which must come back exactly zero
ZERO_TAIL = 2 * 256 + 212


def _wire_buf(key, K, n, scale):
    """(K, n) random rows; a FAT case ends in ``ZERO_TAIL`` zeros."""
    buf = jax.random.normal(key, (K, n)) * scale
    if (K, n) not in FAT:
        return buf
    from repro.kernels.comm import tile_rows
    nb = -(-n // 256)
    for operands in (1, 3):                   # plain pass, EF pass
        r = tile_rows(K, 256, nb, operands)
        assert nb > 2 * r and nb % r, (K, n, r)
    return buf.at[:, -ZERO_TAIL:].set(0.0)


def _assert_zero_tail(K, n, *outs):
    if (K, n) in FAT:
        for o in outs:
            np.testing.assert_array_equal(np.asarray(o)[..., -ZERO_TAIL:], 0)


@pytest.mark.parametrize("K,n", [
    (1, 8 * 256),        # single participant, exactly one (ROWS, block) tile
    (3, 16 * 256),       # odd K, multiple tiles
    (5, 8 * 256 + 300),  # ragged n: kernel pads to whole tiles internally
    *FAT,                # several grid tiles, the last partial
])
def test_quant_avg_dequant_matches_ref(K, n):
    buf = _wire_buf(KEY, K, n, 3)
    m_ref = ref.quant_avg_dequant_ref(buf)
    m_pal = ops.quant_avg_dequant(buf, impl="interpret")
    assert m_pal.shape == (n,)
    # one f32 ULP of slack: the cross-K accumulation order may differ
    np.testing.assert_allclose(np.asarray(m_pal), np.asarray(m_ref),
                               rtol=1e-7, atol=1e-6)
    _assert_zero_tail(K, n, m_pal)


def test_quant_avg_dequant_is_quantized_mean():
    """The fused pass == mean of independently int8-roundtripped rows, and
    sits within the int8 error bound of the exact mean."""
    K, n = 4, 8 * 256
    buf = jax.random.normal(KEY, (K, n)) * 2
    rows = []
    for k in range(K):
        q, s, shp = ref.quantize_blockwise_ref(buf[k])
        rows.append(ref.dequantize_blockwise_ref(q, s, shp))
    expect = jnp.stack(rows).sum(0) / K
    got = ops.quant_avg_dequant(buf, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-7, atol=1e-6)
    exact = np.asarray(buf.mean(0))
    bound = np.abs(np.asarray(buf)).max() / 127.0 + 1e-6
    assert np.abs(np.asarray(got) - exact).max() <= bound


# ---------------------------------------------------------------------------
# sub-int8 bit widths (packed int4, 1-bit sign) + error feedback
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits,qmax", [(8, 127.0), (4, 7.0)])
@pytest.mark.parametrize("shape", [(1000, 37), (256,), (3 * 256 + 100,)])
def test_quantize_bits_roundtrip_bound(bits, qmax, shape):
    x = jax.random.normal(KEY, shape) * 5
    q_p, s_p, shp = ops.quantize_blockwise(x, bits=bits, impl="interpret")
    q_r, s_r, _ = ref.quantize_blockwise_ref(x, bits=bits)
    dq = np.abs(np.asarray(q_p[:q_r.shape[0]], np.int32)
                - np.asarray(q_r, np.int32))
    assert dq.max() <= 1 and (dq > 0).mean() < 1e-3
    x_back = ops.dequantize_blockwise(q_p, s_p, shp, bits=bits,
                                      impl="interpret")
    assert x_back.shape == shape
    scale = float(jnp.abs(x).max())
    err = float(jnp.abs(x - x_back).max())
    assert err <= scale / qmax + 1e-6


@pytest.mark.parametrize("shape", [(256,), (1000, 37)])
def test_quantize_1bit_semantics(shape):
    """1-bit codes are the sign; the per-block scale is mean(|x|)."""
    from repro.kernels.quantize import DEFAULT_BLOCK, unpack_codes
    x = jax.random.normal(KEY, shape) * 3
    q, s, shp = ref.quantize_blockwise_ref(x, bits=1)
    assert q.shape[-1] == DEFAULT_BLOCK // 8     # packed wire payload
    flat = np.asarray(x).reshape(-1)
    pad = -len(flat) % DEFAULT_BLOCK
    flat = np.pad(flat, (0, pad))
    blocks = flat.reshape(-1, DEFAULT_BLOCK)
    np.testing.assert_array_equal(np.asarray(unpack_codes(q, 1), np.int32),
                                  np.where(blocks > 0, 1, -1))
    np.testing.assert_allclose(np.asarray(s),
                               np.abs(blocks).mean(axis=1), rtol=1e-6)
    back = ref.dequantize_blockwise_ref(q, s, shp, bits=1)
    assert back.shape == shape
    # sign * mean|x| keeps every element within 2*mean|x| of the input
    err = np.abs(np.asarray(back) - np.asarray(x).reshape(back.shape))
    assert err.max() <= 2 * np.abs(np.asarray(x)).max()


@pytest.mark.parametrize("bits", [8, 4, 1])
def test_pack_unpack_codes_roundtrip(bits):
    from repro.kernels.quantize import pack_codes, unpack_codes
    lo, hi = (-1, 2) if bits == 1 else (-(2 ** (bits - 1) - 1),
                                        2 ** (bits - 1))
    q = jax.random.randint(KEY, (6, 256), lo, hi, jnp.int32)
    if bits == 1:
        q = jnp.where(q >= 0, 1, -1)       # valid 1-bit codes are +-1
    q = q.astype(jnp.int8)
    p = pack_codes(q, bits)
    assert p.dtype == jnp.int8 if bits == 8 else p.dtype == jnp.uint8
    assert p.shape[-1] == 256 * bits // 8
    back = unpack_codes(p, bits)
    np.testing.assert_array_equal(np.asarray(back, np.int32),
                                  np.asarray(q, np.int32))
    if bits == 8:
        assert p is q                      # identity, not a copy


@pytest.mark.parametrize("bits", [8, 4, 1])
@pytest.mark.parametrize("K,n", [(3, 16 * 256), (5, 8 * 256 + 300), *FAT])
def test_quant_avg_dequant_bits_matches_ref(bits, K, n):
    buf = _wire_buf(KEY, K, n, 3)
    m_ref = ref.quant_avg_dequant_ref(buf, bits=bits)
    m_pal = ops.quant_avg_dequant(buf, bits=bits, impl="interpret")
    assert m_pal.shape == (n,)
    np.testing.assert_allclose(np.asarray(m_pal), np.asarray(m_ref),
                               rtol=2e-6, atol=2e-6)
    _assert_zero_tail(K, n, m_pal)


@pytest.mark.parametrize("bits,K,n", [
    *[pytest.param(b, 3, 8 * 256 + 300, id=str(b)) for b in (8, 4, 1)],
    *[pytest.param(b, K, n, id=f"{b}-fat-K{K}") for K, n in FAT
      for b in (8, 4, 1)],
])
def test_quant_avg_dequant_ef_oracle_and_kernel(bits, K, n):
    """EF fused pass: mean == plain pass on (buf + residual); new residual
    is exactly (buf + residual) - per-row dequant. Kernel == oracle."""
    k1, k2 = jax.random.split(KEY)
    buf = _wire_buf(k1, K, n, 2)
    res = _wire_buf(k2, K, n, 0.1)
    m_ref, e_ref = ref.quant_avg_dequant_ef_ref(buf, res, bits=bits)
    m_pal, e_pal = ops.quant_avg_dequant_ef(buf, res, bits=bits,
                                            impl="interpret")
    np.testing.assert_allclose(np.asarray(m_pal), np.asarray(m_ref),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(e_pal), np.asarray(e_ref),
                               rtol=2e-6, atol=2e-6)
    _assert_zero_tail(K, n, m_pal, e_pal)
    # the mean is the plain fused pass over the compensated buffer
    m_plain = ref.quant_avg_dequant_ref(buf + res, bits=bits)
    np.testing.assert_allclose(np.asarray(m_ref), np.asarray(m_plain),
                               rtol=1e-6, atol=1e-6)
    # residual identity: y - dequant(quant(y)) row by row
    for k in range(K):
        q, s, shp = ref.quantize_blockwise_ref(buf[k] + res[k], bits=bits)
        dq = ref.dequantize_blockwise_ref(q, s, shp, bits=bits)
        np.testing.assert_allclose(np.asarray(e_ref[k]),
                                   np.asarray(buf[k] + res[k] - dq),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bits", [8, 4, 1])
def test_quant_avg_dequant_ef_zero_residual_is_plain(bits):
    K, n = 4, 8 * 256
    buf = jax.random.normal(KEY, (K, n)) * 2
    m_plain = ref.quant_avg_dequant_ref(buf, bits=bits)
    m_ef, e = ref.quant_avg_dequant_ef_ref(buf, jnp.zeros_like(buf),
                                           bits=bits)
    np.testing.assert_array_equal(np.asarray(m_ef), np.asarray(m_plain))
    # the residual is bounded by the quantization step of each block
    assert np.isfinite(np.asarray(e)).all()


def test_check_bits_rejects_unknown_widths():
    from repro.kernels.quantize import check_bits
    for bad in (2, 3, 16, 0):
        with pytest.raises(ValueError):
            check_bits(bad)


# ---------------------------------------------------------------------------
# impl selection: "pallas" is the compiled kernel or an error, never a
# silent interpret-mode or reference run
# ---------------------------------------------------------------------------
def test_pallas_impl_raises_on_cpu_instead_of_interpreting():
    buf = jax.random.normal(KEY, (2, 4096), jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        ops.quant_avg_dequant(buf, impl="pallas")


def test_default_impl_is_the_reference_off_tpu():
    from repro.core import api
    assert jax.default_backend() == "cpu"
    assert ops.resolve_impl() == "ref"
    assert api.FlatFusedInt8().impl == "ref"
    assert api.get_codec("leafwise", bits=4).impl == "ref"
    assert api.FlatFusedIntN(impl="interpret").impl == "interpret"
