"""The decoder with latent attention and sparse experts (``model_type``
``deepseek_v3``), held as one chip's share of an expert-parallel
deployment: the program's configuration, the parameter tree, the plain
reference and its FLOP count.

Nothing here imports the program, except ``program_config``, which builds
the program's own ``ModelConfig``. The reference is written from the
configuration alone, after DeepSeek-V3 (arXiv:2412.19437, section 2.1):

* a pre-norm decoder with RMSNorm; the first ``first_k_dense_replace``
  layers have a SwiGLU feed-forward, the rest an expert layer;
* latent attention in its plain form: queries from one projection ``w_q``
  (``q_lora_rank`` null) or through a normed low rank; keys and values
  taken up from the normed latent ``c_kv`` per head, and one RoPE key
  shared by the heads; RoPE over the ``qk_rope_head_dim`` dims, halves
  rotated together; causal softmax attention at 1/sqrt(nope + rope),
  computed in query blocks under ``jax.checkpoint``;
* the router over all ``n_routed_experts x ep_size`` experts of the
  deployment: sigmoid scores, top-k chosen on the scores plus the
  correction bias, weights the chosen scores over their sum times
  ``routed_scaling_factor``; the ``n_routed_experts`` experts held here
  (the first ones) each run over every token, weighted by its gate or by
  0, and the shared experts over every token;
* the sequence-wise balance loss (``seq_aux``) alpha x sum_i f_i P_i of
  each sequence, averaged over the batch, summed over the expert layers
  and added to the mean next-token cross-entropy, as the program's loss
  adds it.

It computes in the dtype of its parameters with every matrix product at
the precision it is given. The parameter tree is the one the program's
decoder takes: ``embed``, ``final_norm``, ``head``, and a segment a
layer (the dense layers, then the expert layers), each ``p0`` with a
stack of one; the routing bias is a ``"zeros"`` leaf.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

#: query rows a block of the reference's attention
Q_BLOCK = 512


def dims(cfg):
    """The sizes the reference and the FLOP count read from a config."""
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    held = cfg["n_routed_experts"]
    return {
        "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "ql": cfg.get("q_lora_rank") or 0, "kl": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "vh": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
        "f": cfg["moe_intermediate_size"], "held": held,
        "E": held * cfg.get("ep_size", 1), "k": cfg["num_experts_per_tok"],
        "shared": cfg["n_shared_experts"], "V": cfg["vocab_size"],
        "L": L, "dense": dense, "moe": L - dense,
        "tie": bool(cfg.get("tie_word_embeddings", False)),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "alpha": float(cfg["seq_aux_alpha"]),
    }


def _refuse(cfg):
    """Raise a ``SpecError`` on what the program's decoder cannot run."""
    from bench.spec import SpecError
    want = {"hidden_act": "silu", "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "norm_topk_prob": True,
            "seq_aux": True, "moe_layer_freq": 1}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise SpecError(f"{key} {cfg[key]!r}: the program runs "
                            f"{value!r}")
    for key in ("n_group", "topk_group"):
        if cfg.get(key, 1) > 1:
            raise SpecError(f"{key} {cfg[key]}: the program's router "
                            "chooses among all experts, in one group")
    if cfg.get("num_nextn_predict_layers", 0):
        raise SpecError("num_nextn_predict_layers: the benchmark runs no "
                        "multi-token prediction")
    for key in ("attention_bias", "rope_scaling"):
        if cfg.get(key):
            raise SpecError(f"{key}: the program's latent attention has "
                            "none")


def program_config(cfg):
    """The program's ``ModelConfig`` for a benchmark configuration file.

    Fails where the file states what the program's decoder cannot run,
    and where the program has no held share of experts or no sigmoid
    router (``bench.spec.SpecError``)."""
    from bench.spec import SpecError
    from repro.configs.base import ModelConfig
    _refuse(cfg)
    have = {f.name for f in dataclasses.fields(ModelConfig)}
    missing = {"router_score", "routed_scale", "experts_held"} - have
    if missing:
        raise SpecError(f"the program's ModelConfig has no {sorted(missing)}"
                        ": it cannot hold a share of the experts")
    m = dims(cfg)
    # a segment a layer: scanned over a stack of layers under the
    # participants' vmap, the backward pass would lay the stacked weight
    # gradients out layer-major and copy them, 4.4 GB at the cell's size
    segments = tuple(((kind,), 1) for kind in
                     ["mla:dense"] * m["dense"] + ["mla:moe"] * m["moe"])
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["H"], d_ff=m["ff"], vocab_size=m["V"],
        segments=segments,
        norm_eps=m["eps"], rope_theta=m["theta"], tie_embeddings=m["tie"],
        q_lora_rank=m["ql"], kv_lora_rank=m["kl"], qk_nope_dim=m["nope"],
        qk_rope_dim=m["rope"], v_head_dim=m["vh"], n_experts=m["E"],
        top_k=m["k"], moe_d_ff=m["f"], n_shared_experts=m["shared"],
        router_aux_coef=m["alpha"], router_score="sigmoid",
        routed_scale=m["scale"], experts_held=m["held"])


def _attention_shapes(m, n):
    d, H, ql, kl = m["d"], m["H"], m["ql"], m["kl"]
    qk = m["nope"] + m["rope"]
    if ql:
        q = {"w_dq": ((n, d, ql), d), "q_norm_g": ((n, ql), "ones"),
             "w_uq": ((n, ql, H, qk), ql)}
    else:
        q = {"w_q": ((n, d, H, qk), d)}
    return {**q, "w_dkv": ((n, d, kl), d), "kv_norm_g": ((n, kl), "ones"),
            "w_kr": ((n, d, m["rope"]), d),
            "w_uk": ((n, kl, H, m["nope"]), kl),
            "w_uv": ((n, kl, H, m["vh"]), kl),
            "w_o": ((n, H, m["vh"], d), H * m["vh"])}


def param_shapes(cfg):
    """The parameter tree as ``(shape, init)`` leaves: a fan-in, ``"ones"``
    for a norm gain or ``"zeros"`` for the routing bias."""
    m = dims(cfg)
    d, ff, f, held, V = m["d"], m["ff"], m["f"], m["held"], m["V"]
    fs = f * m["shared"]

    def layer(ffn):
        return {"p0": {"norm1": {"g": ((1, d), "ones")},
                       "mixer": _attention_shapes(m, 1),
                       "norm2": {"g": ((1, d), "ones")}, "ffn": ffn}}
    dense = {"wi": ((1, d, ff), d), "wg": ((1, d, ff), d),
             "wo": ((1, ff, d), ff)}
    moe = {"router": ((1, d, m["E"]), d),
           "router_bias": ((1, m["E"]), "zeros"),
           "wi": ((1, held, d, f), d), "wg": ((1, held, d, f), d),
           "wo": ((1, held, f, d), f)}
    if m["shared"]:
        moe["shared"] = {"wi": ((1, d, fs), d), "wg": ((1, d, fs), d),
                         "wo": ((1, fs, d), fs)}
    segments = [layer(dense)] * m["dense"] + [layer(moe)] * m["moe"]
    tree = {"embed": {"table": ((V, d), d)},
            "final_norm": {"g": ((d,), "ones")}, "segments": segments}
    if not m["tie"]:
        tree["head"] = {"w": ((d, V), d)}
    return tree


def _rmsnorm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, theta):
    """Rotary embedding over the last dim of ``x`` (B, S, ..., r), halves
    rotated together, position = index along S."""
    S, r = x.shape[1], x.shape[-1]
    half = r // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1, S) + (1,) * (x.ndim - 3) + (half,)
    cos = jnp.cos(ang).reshape(shape).astype(x.dtype)
    sin = jnp.sin(ang).reshape(shape).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, m, ein):
    """Causal latent attention in its plain form, in query blocks."""
    dt = h.dtype
    if "w_q" in p:
        q = ein("bsd,dhe->bshe", h, p["w_q"])
    else:
        cq = _rmsnorm(ein("bsd,dq->bsq", h, p["w_dq"]), p["q_norm_g"],
                      m["eps"])
        q = ein("bsq,qhe->bshe", cq, p["w_uq"])
    q_nope = q[..., :m["nope"]]
    q_rope = _rope(q[..., m["nope"]:], m["theta"])
    c = _rmsnorm(ein("bsd,dk->bsk", h, p["w_dkv"]), p["kv_norm_g"], m["eps"])
    k_rope = _rope(ein("bsd,dr->bsr", h, p["w_kr"]), m["theta"])
    k_nope = ein("bsk,khn->bshn", c, p["w_uk"])
    v = ein("bsk,khv->bshv", c, p["w_uv"])
    scale = jnp.asarray((m["nope"] + m["rope"]) ** -0.5, dt)
    S = h.shape[1]

    @jax.checkpoint
    def block(qn, qr, kn, kr, vv, lo):
        n, hi = qn.shape[1], kn.shape[1]
        s = (ein("bqhn,bshn->bhqs", qn, kn)
             + ein("bqhr,bsr->bhqs", qr, kr)) * scale
        causal = (jnp.arange(hi)[None, :]
                  <= lo + jnp.arange(n)[:, None])
        s = jnp.where(causal[None, None], s, jnp.asarray(-jnp.inf, dt))
        return ein("bhqs,bshv->bqhv", jax.nn.softmax(s, axis=-1), vv)

    outs = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(lo + Q_BLOCK, S)       # keys past the block see nothing
        outs.append(block(q_nope[:, lo:hi], q_rope[:, lo:hi], k_nope[:, :hi],
                          k_rope[:, :hi], v[:, :hi], lo))
    o = jnp.concatenate(outs, axis=1)
    return ein("bqhv,hvd->bqd", o, p["w_o"])


def _swiglu(x, wi, wg, wo, ein):
    return ein("...f,fd->...d", jax.nn.silu(ein("...d,df->...f", x, wg))
               * ein("...d,df->...f", x, wi), wo)


def _experts(p, h, m, ein):
    """The held experts' part of the layer, the shared experts, and the
    layer's sequence-wise balance loss."""
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    logits = ein("td,de->te", x.astype(jnp.float32),
                 p["router"].astype(jnp.float32))
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                           m["k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weight = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * m["scale"]
    picked = jax.nn.one_hot(idx, m["E"], dtype=jnp.float32)     # (T,k,E)
    gate = jnp.sum(weight[..., None] * picked, axis=1)          # 0 unpicked
    up = ein("td,edf->etf", x, p["wi"])
    act = jax.nn.silu(ein("td,edf->etf", x, p["wg"])) * up
    out = ein("etf,efd->etd", act, p["wo"])
    y = ein("te,etd->td", gate[:, :m["held"]].astype(x.dtype), out)
    if "shared" in p:
        s = p["shared"]
        y = y + _swiglu(x, s["wi"], s["wg"], s["wo"], ein)
    # f_i = E/(k S) x picks of expert i in the sequence; P_i = the mean of
    # its score normalised over all E
    f = picked.sum(1).reshape(B, S, m["E"]).sum(1) * (m["E"] / (m["k"] * S))
    P = (scores / scores.sum(-1, keepdims=True)).reshape(B, S, -1).mean(1)
    aux = m["alpha"] * jnp.mean(jnp.sum(f * P, axis=-1))
    return y.reshape(B, S, d), aux


def loss(params, tokens, labels, cfg, prec):
    """Mean next-token cross-entropy of one batch plus the expert layers'
    sequence-wise balance loss; computes in the dtype of ``params``."""
    m = dims(cfg)
    ein = functools.partial(jnp.einsum, precision=prec)
    x = params["embed"]["table"][tokens]                     # (B, S, d)
    aux = jnp.zeros((), jnp.float32)

    @jax.checkpoint
    def layer(x, p):
        h = _rmsnorm(x, p["norm1"]["g"], m["eps"])
        x = x + _attention(p["mixer"], h, m, ein)
        h = _rmsnorm(x, p["norm2"]["g"], m["eps"])
        if "router" not in p["ffn"]:
            f = p["ffn"]
            return x + _swiglu(h, f["wi"], f["wg"], f["wo"], ein), 0.0
        y, a = _experts(p["ffn"], h, m, ein)
        return x + y, a
    for seg in params["segments"]:
        x, a = layer(x, jax.tree.map(lambda t: t[0], seg["p0"]))
        aux = aux + a
    h = _rmsnorm(x, params["final_norm"]["g"], m["eps"])
    if m["tie"]:
        logits = ein("bsd,vd->bsv", h, params["embed"]["table"])
    else:
        logits = ein("bsd,dv->bsv", h, params["head"]["w"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked) + aux.astype(logits.dtype)


def train_flops_per_token(cfg, seq_len):
    """Model FLOPs of one training token (forward and backward): 6 a
    matmul weight the token passes through -- the attention projections,
    the dense feed-forward, the router, the shared experts, the held
    routed experts it reaches on average (k x held / E of them), the
    head -- plus the model's own causal attention, a layer and a token: 2
    products over H x (nope + rope) and H x v_head_dim at the mean
    visible context (S + 1) / 2, 2 FLOPs each, times 3 for the backward
    pass. What the program does beyond that -- the chunk pairs past the
    diagonal, the forwards its remat runs again -- is not counted."""
    m = dims(cfg)
    d, H, ql, kl = m["d"], m["H"], m["ql"], m["kl"]
    qk = m["nope"] + m["rope"]
    q = d * ql + ql * H * qk if ql else d * H * qk
    attn = q + d * kl + d * m["rope"] + kl * H * (m["nope"] + m["vh"]) \
        + H * m["vh"] * d
    dense = 3 * d * m["ff"]
    expert = 3 * d * m["f"]
    moe = (d * m["E"] + m["shared"] * expert
           + m["k"] * m["held"] / m["E"] * expert)
    head = d * m["V"]
    weights = m["L"] * attn + m["dense"] * dense + m["moe"] * moe + head
    causal = 3 * 2 * H * (qk + m["vh"]) * (seq_len + 1) / 2 * m["L"]
    return 6 * weights + causal
