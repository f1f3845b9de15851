"""The dense decoder with grouped-query attention (``model_type``
``internlm2``): the program's configuration, the parameter tree, the plain
reference and its FLOP count.

Nothing here imports the program, except ``program_config``, which builds
the program's own ``ModelConfig``. The reference is written from the
configuration alone: a pre-norm decoder with RMSNorm, rotary embeddings
over whole heads (``rotate_half`` layout), causal grouped-query attention
and a SwiGLU feed-forward, and the mean next-token cross-entropy. It
computes in the dtype of its parameters with every matrix product at the
precision it is given. The parameter tree is the one the program's
decoder takes (``embed``, ``final_norm``, ``head`` unless the embeddings
are tied, one segment ``p0`` whose leaves stack the layers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims(cfg):
    """The sizes the reference and the FLOP count read from a config."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {
        "d": d, "H": H, "KV": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // H,
        "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "L": cfg["num_hidden_layers"],
        "tie": bool(cfg.get("tie_word_embeddings", False)),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
    }


def program_config(cfg):
    """The program's ``ModelConfig`` for a benchmark configuration file.

    Fails where the file states what the program's decoder cannot run."""
    from repro.configs.base import ModelConfig
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: the program's "
                         "feed-forward is SwiGLU")
    if float(cfg.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("the program's decoder rotates whole heads only")
    for k in ("bias", "attention_bias", "mlp_bias", "lm_head_bias"):
        if cfg.get(k):
            raise ValueError(f"{k}: the program's decoder has no biases")
    m = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["KV"], d_ff=m["ff"],
        vocab_size=m["V"], head_dim=m["hd"], norm_eps=m["eps"],
        rope_theta=m["theta"], tie_embeddings=m["tie"])


def param_shapes(cfg):
    """The parameter tree as ``(shape, init)`` leaves: a fan-in, or
    ``"ones"`` for a norm gain."""
    m = dims(cfg)
    d, H, KV, hd, ff, V, L = (m[k] for k in ("d", "H", "KV", "hd", "ff",
                                             "V", "L"))
    tree = {
        "embed": {"table": ((V, d), d)},
        "final_norm": {"g": ((d,), "ones")},
        "segments": [{"p0": {
            "norm1": {"g": ((L, d), "ones")},
            "mixer": {"wq": ((L, d, H, hd), d), "wk": ((L, d, KV, hd), d),
                      "wv": ((L, d, KV, hd), d),
                      "wo": ((L, H, hd, d), H * hd)},
            "norm2": {"g": ((L, d), "ones")},
            "ffn": {"wi": ((L, d, ff), d), "wg": ((L, d, ff), d),
                    "wo": ((L, ff, d), ff)},
        }}],
    }
    if not m["tie"]:
        tree["head"] = {"w": ((d, V), d)}
    return tree


def _rmsnorm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, theta):
    """Rotary embedding over the whole head, halves rotated together."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(params, tokens, labels, cfg, prec):
    """Mean next-token cross-entropy of one batch; computes in the dtype
    of ``params``. The program's loss carries no auxiliary term for this
    architecture."""
    m = dims(cfg)
    ein = functools.partial(jnp.einsum, precision=prec)
    dt = params["embed"]["table"].dtype
    x = params["embed"]["table"][tokens]                     # (B, S, d)
    S = tokens.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    G = m["H"] // m["KV"]
    lay = params["segments"][0]["p0"]
    for i in range(m["L"]):
        p = jax.tree.map(lambda t, i=i: t[i], lay)
        h = _rmsnorm(x, p["norm1"]["g"], m["eps"])
        q = _rope(ein("bsd,dhk->bshk", h, p["mixer"]["wq"]), m["theta"])
        k = _rope(ein("bsd,dhk->bshk", h, p["mixer"]["wk"]), m["theta"])
        v = ein("bsd,dhk->bshk", h, p["mixer"]["wv"])
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        s = ein("bqhk,bshk->bhqs", q, k) * jnp.asarray(m["hd"] ** -0.5, dt)
        s = jnp.where(causal[None, None], s, jnp.asarray(-jnp.inf, dt))
        w = jax.nn.softmax(s, axis=-1)
        o = ein("bhqs,bshk->bqhk", w, v)
        x = x + ein("bqhk,hkd->bqd", o, p["mixer"]["wo"])
        h = _rmsnorm(x, p["norm2"]["g"], m["eps"])
        up = ein("bsd,df->bsf", h, p["ffn"]["wi"])
        gate = ein("bsd,df->bsf", h, p["ffn"]["wg"])
        x = x + ein("bsf,fd->bsd", jax.nn.silu(gate) * up, p["ffn"]["wo"])
    h = _rmsnorm(x, params["final_norm"]["g"], m["eps"])
    if m["tie"]:
        logits = ein("bsd,vd->bsv", h, params["embed"]["table"])
    else:
        logits = ein("bsd,dv->bsv", h, params["head"]["w"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def matmul_params(cfg):
    """Weights that enter a matrix product once a token: the attention
    projections, the feed-forward and the output head (the tied embedding
    counts once, as the head; its row gather counts nothing)."""
    m = dims(cfg)
    d, H, KV, hd, ff, V, L = (m[k] for k in ("d", "H", "KV", "hd", "ff",
                                             "V", "L"))
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    ffn = 3 * d * ff
    return L * (attn + ffn) + d * V


def train_flops_per_token(cfg, seq_len):
    """Model FLOPs of one training token (forward and backward): 6 a
    matmul weight, plus the causal attention, a layer and a token, 2
    products of 2 FLOPs over H x head_dim x the mean visible context
    (S + 1) / 2, times 3 for the backward pass."""
    m = dims(cfg)
    attn = 3 * 2 * 2 * m["H"] * m["hd"] * (seq_len + 1) / 2 * m["L"]
    return 6 * matmul_params(cfg) + attn
