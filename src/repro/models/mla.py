"""Multi-head Latent Attention (DeepSeek-V2/V3, arXiv:2405.04434 §2.1,
arXiv:2412.19437).

Queries come from a low-rank path (``w_dq``, RMSNorm ``q_norm_g``,
``w_uq``) when ``cfg.q_lora_rank`` > 0, and from one projection ``w_q``
(d, H, nope + rope) with no query norm when it is 0 (DeepSeek-V2-Lite,
Moonlight). Keys and values come from the shared latent ``c_kv`` (``w_dkv``
then RMSNorm) and one decoupled RoPE key (``w_kr``) that every head
shares; RoPE rotates the ``qk_rope_dim`` dims in ``apply_rope``'s
half-split layout.

Training and prefill (``mla_apply``) run the *expanded* form, as the
model is trained: each head's keys ``k_nope = c_kv @ W_uk`` and values
``v = c_kv @ W_uv`` are taken up from the latent, so the heads attend over
nope + rope key dims and v_head_dim value dims through the multi-head
chunked path, which recomputes each chunk pair's scores in the backward
pass instead of saving them.

Decode (``mla_decode``) runs the *absorbed* form over the latent cache,
which stores only ``(c_kv, k_rope)`` — the paper's KV-cache compression:
queries are taken into the latent space (q_eff = q_nope @ W_uk), every
head attends over the one shared latent key of width ``kv_lora_rank`` +
``qk_rope_dim``, and the latent output is taken up by W_uv after
attention. Both forms compute the same function. The sliding window (for
long_500k) applies to the latent cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import MLA
from repro.models.attention import chunked_attention, NEG_INF
from repro.models.layers import apply_rope, rmsnorm_apply, trunc_normal


def mla_init(key, cfg, dtype, stack=()):
    d = cfg.d_model
    H, ql, kl = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    if ql:
        q = {"w_dq": trunc_normal(ks[0], (*stack, d, ql), d ** -0.5, dtype),
             "q_norm_g": jnp.ones((*stack, ql), dtype),
             "w_uq": trunc_normal(ks[1], (*stack, ql, H, nope + rope),
                                  ql ** -0.5, dtype)}
    else:
        q = {"w_q": trunc_normal(ks[0], (*stack, d, H, nope + rope),
                                 d ** -0.5, dtype)}
    return {
        **q,
        "w_dkv": trunc_normal(ks[2], (*stack, d, kl), d ** -0.5, dtype),
        "kv_norm_g": jnp.ones((*stack, kl), dtype),
        "w_kr": trunc_normal(ks[3], (*stack, d, rope), d ** -0.5, dtype),
        "w_uk": trunc_normal(ks[4], (*stack, kl, H, nope), kl ** -0.5, dtype),
        "w_uv": trunc_normal(ks[5], (*stack, kl, H, vh), kl ** -0.5, dtype),
        "w_o": trunc_normal(ks[6], (*stack, H, vh, d), (H * vh) ** -0.5, dtype),
    }


def _projections(p, x, cfg, positions):
    """Returns q_nope (B,S,H,nope), q_rope (B,S,H,rope) after RoPE, the
    normed latent c_kv (B,S,kl) and k_rope (B,S,rope) after RoPE."""
    if "w_q" in p:
        q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    else:
        cq = jnp.einsum("bsd,dq->bsq", x, p["w_dq"])
        cq = rmsnorm_apply({"g": p["q_norm_g"]}, cq, cfg.norm_eps)
        q = jnp.einsum("bsq,qhe->bshe", cq, p["w_uq"])
    nope = cfg.qk_nope_dim
    q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    c_kv = jnp.einsum("bsd,dk->bsk", x, p["w_dkv"])
    c_kv = rmsnorm_apply({"g": p["kv_norm_g"]}, c_kv, cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_kr"])
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return q[..., :nope], q_rope, c_kv, k_rope


def _scale(cfg):
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5


def _latents(p, x, cfg, positions):
    """The absorbed form's inputs: q_eff (B,S,H,kl+rope), c_kv (B,S,kl),
    k_rope (B,S,rope)."""
    q_nope, q_rope, c_kv, k_rope = _projections(p, x, cfg, positions)
    # absorb: q_eff_latent = q_nope @ W_uk  -> (B,S,H,kl)
    q_eff = jnp.einsum("bshn,khn->bshk", q_nope, p["w_uk"])
    return jnp.concatenate([q_eff, q_rope], -1), c_kv, k_rope


def _out_proj(p, o_latent, cfg):
    """o_latent: (B,S,H,kl) -> (B,S,D) via per-head W_uv then W_o."""
    o = jnp.einsum("bshk,khv->bshv", o_latent, p["w_uv"])
    return jnp.einsum("bshv,hvd->bsd", o, p["w_o"])


def mla_apply(p, x, cfg, positions, impl="ref"):
    """Training/prefill forward in the expanded form; returns (y, (c_kv,
    k_rope)) for caching."""
    with jax.named_scope(MLA):
        q_nope, q_rope, c_kv, k_rope = _projections(p, x, cfg, positions)
        H = cfg.n_heads
        k_nope = jnp.einsum("bsk,khn->bshn", c_kv, p["w_uk"])
        v = jnp.einsum("bsk,khv->bshv", c_kv, p["w_uv"])
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None], (*k_rope.shape[:2], H, k_rope.shape[-1]))],
            -1)
        o = chunked_attention(q, k, v, n_kv_heads=H, window=cfg.window,
                              softmax_scale=_scale(cfg), recompute=True)
        y = jnp.einsum("bshv,hvd->bsd", o, p["w_o"])
        return y, (c_kv, k_rope)


def mla_cache_init(cfg, batch, seq_len, dtype):
    S = min(cfg.window, seq_len) if cfg.window else seq_len
    return {"c_kv": jnp.zeros((batch, S, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, S, cfg.qk_rope_dim), dtype)}


def mla_decode(p, x, cfg, cache, pos):
    """One token in the absorbed form against the latent cache."""
    B = x.shape[0]
    S = cache["c_kv"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_all, c_kv, k_rope = _latents(p, x, cfg, positions)
    slot = pos % S if cfg.window else pos
    cc = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, slot, 0))
    cr = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, slot, 0))
    kv = jnp.concatenate([cc, cr], -1)                         # (B,S,kl+r)
    qh = (q_all * _scale(cfg))[:, 0]                           # (B,H,kl+r)
    s = jnp.einsum("bhd,bsd->bhs", qh, kv).astype(jnp.float32)
    idx = jnp.arange(S)
    valid = ((idx <= pos) | (pos >= S)) if cfg.window else (idx <= pos)
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_latent = jnp.einsum("bhs,bsk->bhk", w.astype(cc.dtype), cc)[:, None]
    return _out_proj(p, o_latent, cfg), {"c_kv": cc, "k_rope": cr}
