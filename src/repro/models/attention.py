"""GQA attention: chunked-flash training/prefill path + KV-cache decode.

The training path is an online-softmax chunked attention written in pure
jnp/lax (the CPU-lowering oracle); on TPU the same contract is served by
``repro.kernels.flash_attention`` (selected via ``impl='pallas'``).

Sharding note: GQA keeps the full H head dim intact through every einsum —
KV heads are repeated to H at compute time (cheap: they are replicated or
resliced, never stored repeated in the cache) — because reshaping H into
(KV, G) breaks GSPMD head-sharding propagation (measured: 16x compute
replication on the model axis). MQA/MLA (KV=1) uses a shared-KV einsum
with no repetition at all. `constrain` pins the head dim to the `model`
mesh axis whenever divisible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, trunc_normal
from repro.sharding.constrain import constrain

NEG_INF = -1e30


def attn_init(key, cfg, dtype, stack=()):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": trunc_normal(ks[0], (*stack, d, H, hd), d ** -0.5, dtype),
        "wk": trunc_normal(ks[1], (*stack, d, KV, hd), d ** -0.5, dtype),
        "wv": trunc_normal(ks[2], (*stack, d, KV, hd), d ** -0.5, dtype),
        "wo": trunc_normal(ks[3], (*stack, H, hd, d), (H * hd) ** -0.5, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((*stack, H, hd), dtype)
        p["bk"] = jnp.zeros((*stack, KV, hd), dtype)
        p["bv"] = jnp.zeros((*stack, KV, hd), dtype)
    return p


def _qkv(p, x, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def repeat_kv(k, n_heads):
    """(B,S,KV,hd) -> (B,S,H,hd); H-dim then constrained to `model`."""
    KV = k.shape[2]
    if KV == n_heads:
        return k
    k = jnp.repeat(k, n_heads // KV, axis=2)
    return constrain(k, (None, None, "model", None))


def mask_bias(q_pos, k_pos, window):
    """(Sq,Sk) additive mask: causal, optional sliding window."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def chunked_attention(q, k, v, *, n_kv_heads, window=0, q_offset=0,
                      chunk_q=1024, chunk_kv=1024, softmax_scale=None,
                      recompute=False):
    """Online-softmax attention. q:(B,Sq,H,hd) k,v:(B,Sk,KV,hd/hd_v).

    Chunked over both Sq (outer scan) and Sk (inner scan) so the peak score
    tensor is (B,H,cq,ck) regardless of sequence length. KV==1 uses the
    shared-KV (MQA/MLA) path. Differentiated as it stands, the scans save every chunk pair's scores
    for the backward pass: the whole (B,H,Sq,Sk) matrix. ``recompute``
    saves only the scans' carries and recomputes each chunk pair's scores
    in the backward pass.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    hd_v = v.shape[-1]
    mqa = n_kv_heads == 1
    scale = softmax_scale or hd ** -0.5

    def _chunk(S, target):                 # largest divisor of S <= target
        c = min(target, S)
        while S % c:
            c -= 1
        return c

    cq, ck = _chunk(Sq, chunk_q), _chunk(Sk, chunk_kv)
    nq, nk = Sq // cq, Sk // ck

    q = constrain(q * scale, (None, None, "model", None))
    if mqa:
        k2, v2 = k[:, :, 0], v[:, :, 0]                    # (B,Sk,hd)
        kg = k2.reshape(B, nk, ck, hd).transpose(1, 0, 2, 3)
        vg = v2.reshape(B, nk, ck, hd_v).transpose(1, 0, 2, 3)
    else:
        k2 = repeat_kv(k, H)
        v2 = repeat_kv(v, H)
        kg = k2.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
        vg = v2.reshape(B, nk, ck, H, hd_v).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(B, nq, cq, H, hd).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx                                # qi: (B,cq,H,hd)
        q_pos = q_offset + iq * cq + jnp.arange(cq)

        def kv_step(carry, kv_and_idx):
            m, l, acc = carry
            (kc, vc), ik = kv_and_idx
            k_pos = ik * ck + jnp.arange(ck)
            if mqa:
                s = jnp.einsum("bqhd,bkd->bhqk", qi, kc).astype(jnp.float32)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", qi, kc).astype(jnp.float32)
            s = constrain(s, (None, "model", None, None))
            s = s + mask_bias(q_pos, k_pos, window)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            if mqa:
                pv = jnp.einsum("bhqk,bkd->bhqd", p.astype(vc.dtype), vc)
            else:
                pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vc.dtype), vc)
            acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, hd_v), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step) if recompute else kv_step, (m0, l0, a0),
            ((kg, vg), jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,H,cq,hd_v)
        return None, out.transpose(0, 2, 1, 3)             # (B,cq,H,hd_v)

    _, outs = jax.lax.scan(jax.checkpoint(q_step) if recompute else q_step,
                           None, (qg, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd_v)
    return out.astype(q.dtype)


def attn_apply(p, x, cfg, positions, impl="ref"):
    """Training/prefill forward. x: (B,S,D) -> (B,S,D), plus (k,v) for cache."""
    q, k, v = _qkv(p, x, cfg, positions)
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, n_kv_heads=cfg.n_kv_heads,
                                   window=cfg.window)
    else:
        out = chunked_attention(q, k, v, n_kv_heads=cfg.n_kv_heads,
                                window=cfg.window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, (k, v)


# --------------------------------------------------------------------------
# Decode (one token, KV cache; ring buffer when cfg.window > 0)
# --------------------------------------------------------------------------
def attn_cache_init(cfg, batch, seq_len, dtype):
    S = min(cfg.window, seq_len) if cfg.window else seq_len
    shp = (batch, S, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


def decode_attend(q, ck, cv, pos, *, window, softmax_scale):
    """q: (B,1,H,hd); ck/cv: (B,S,KV,hd). Single-token attention."""
    B, _, H, hd = q.shape
    S = ck.shape[1]
    qh = q[:, 0] * softmax_scale                           # (B,H,hd)
    k2 = repeat_kv(ck, H)                                  # (B,S,H,hd)
    v2 = repeat_kv(cv, H)
    s = jnp.einsum("bhd,bshd->bhs", qh, k2).astype(jnp.float32)
    idx = jnp.arange(S)
    valid = ((idx <= pos) | (pos >= S)) if window else (idx <= pos)
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", w.astype(v2.dtype), v2)
    return out[:, None]                                    # (B,1,H,hd_v)


def attn_decode(p, x, cfg, cache, pos):
    """x: (B,1,D); pos: () int32 current position. Returns (y, new_cache)."""
    B = x.shape[0]
    S = cache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions)                   # k,v: (B,1,KV,hd)
    slot = pos % S if cfg.window else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    out = decode_attend(q, ck, cv, pos, window=cfg.window,
                        softmax_scale=cfg.head_dim ** -0.5)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": ck, "v": cv}
