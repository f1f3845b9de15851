"""Model assembly: heterogeneous layer stacks, scan/unroll lowering, decode.

A config's ``segments`` is a sequence of (pattern, repeats); each pattern
entry is "<mixer>:<ffn>". Parameters for each pattern position carry a
leading ``repeats`` dim so the production path is a single `lax.scan` per
segment (compact HLO, per-layer remat), while the roofline path unrolls the
same body (`lowering='unroll'`) for accurate XLA cost analysis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import xlstm as xl
from repro.models.layers import (dense_init, embed_apply, embed_init,
                                 ffn_apply, ffn_init, lm_head_apply,
                                 rmsnorm_apply, rmsnorm_init, softmax_xent,
                                 trunc_normal)
from repro.sharding.constrain import constrain


# ---------------------------------------------------------------------------
# Per-layer init / apply / decode dispatch
# ---------------------------------------------------------------------------
def layer_init(key, kind, cfg, dtype, stack=()):
    mixer, ffn = kind.split(":")
    k1, k2 = jax.random.split(key)
    p = {"norm1": rmsnorm_init(cfg.d_model, dtype, stack)}
    p["mixer"] = {
        "gqa": attn.attn_init, "mla": mla_mod.mla_init, "mamba": mam.mamba_init,
        "mlstm": xl.mlstm_init, "slstm": xl.slstm_init,
    }[mixer](k1, cfg, dtype, stack)
    if ffn != "-":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype, stack)
        if ffn == "dense":
            p["ffn"] = ffn_init(k2, cfg.d_model, cfg.d_ff, dtype, stack)
        elif ffn == "moe":
            p["ffn"] = moe_mod.moe_init(k2, cfg, dtype, stack)
        elif ffn == "moe_dense":                       # Arctic: MoE ∥ dense
            ka, kb = jax.random.split(k2)
            p["ffn"] = {"moe": moe_mod.moe_init(ka, cfg, dtype, stack),
                        "dense": ffn_init(kb, cfg.d_model, cfg.d_ff, dtype, stack)}
    return p


def layer_apply(p, kind, x, cfg, positions, impl="ref"):
    mixer, ffn = kind.split(":")
    h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if mixer == "gqa":
        y, _ = attn.attn_apply(p["mixer"], h, cfg, positions, impl)
    elif mixer == "mla":
        y, _ = mla_mod.mla_apply(p["mixer"], h, cfg, positions, impl)
    elif mixer == "mamba":
        y = mam.mamba_apply(p["mixer"], h, cfg, impl)
    elif mixer == "mlstm":
        y = xl.mlstm_apply(p["mixer"], h, cfg, impl)
    else:
        y = xl.slstm_apply(p["mixer"], h, cfg, impl)
    x = x + y
    aux, counters = jnp.zeros((), jnp.float32), {}
    if ffn != "-":
        h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        if ffn == "dense":
            y = ffn_apply(p["ffn"], h)
        elif ffn == "moe":
            y, aux, counters = moe_mod.moe_apply(p["ffn"], h, cfg)
        else:
            ym, aux, counters = moe_mod.moe_apply(p["ffn"]["moe"], h, cfg)
            y = ym + ffn_apply(p["ffn"]["dense"], h)
        x = x + y
    return constrain(x, ("dp", "r", "r")), aux, counters


def layer_cache_init(kind, cfg, batch, seq_len, dtype):
    mixer, _ = kind.split(":")
    if mixer == "gqa":
        return attn.attn_cache_init(cfg, batch, seq_len, dtype)
    if mixer == "mla":
        return mla_mod.mla_cache_init(cfg, batch, seq_len, dtype)
    if mixer == "mamba":
        return mam.mamba_state_init(cfg, batch, dtype)
    if mixer == "mlstm":
        return xl.mlstm_state_init(cfg, batch, dtype)
    return xl.slstm_state_init(cfg, batch, dtype)


def layer_decode(p, kind, x, cfg, cache, pos):
    mixer, ffn = kind.split(":")
    h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    fn = {"gqa": attn.attn_decode, "mla": mla_mod.mla_decode,
          "mamba": mam.mamba_decode, "mlstm": xl.mlstm_decode,
          "slstm": xl.slstm_decode}[mixer]
    y, new_cache = fn(p["mixer"], h, cfg, cache, pos)
    x = x + y
    if ffn != "-":
        h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        if ffn == "dense":
            y = ffn_apply(p["ffn"], h)
        elif ffn == "moe":
            y, _, _ = moe_mod.moe_apply(p["ffn"], h, cfg)
        else:
            ym, _, _ = moe_mod.moe_apply(p["ffn"]["moe"], h, cfg)
            y = ym + ffn_apply(p["ffn"]["dense"], h)
        x = x + y
    return x, new_cache


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------
def init_params(key, cfg, dtype=jnp.bfloat16):
    keys = jax.random.split(key, len(cfg.segments) + 3)
    params = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
              "final_norm": rmsnorm_init(cfg.d_model, dtype),
              "segments": []}
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
    for si, (pattern, repeats) in enumerate(cfg.segments):
        seg = {}
        pkeys = jax.random.split(keys[2 + si], len(pattern))
        for j, kind in enumerate(pattern):
            seg[f"p{j}"] = layer_init(pkeys[j], kind, cfg, dtype, stack=(repeats,))
        params["segments"].append(seg)
    if cfg.mtp_depth:                                   # DeepSeek-V3 MTP head
        km = jax.random.split(keys[-1], 2)
        last_kind = cfg.segments[-1][0][-1]
        params["mtp"] = {
            "proj": dense_init(km[0], 2 * cfg.d_model, cfg.d_model, dtype),
            "norm_h": rmsnorm_init(cfg.d_model, dtype),
            "norm_e": rmsnorm_init(cfg.d_model, dtype),
            "layer": layer_init(km[1], last_kind, cfg, dtype, stack=(1,)),
        }
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def embed_inputs(params, cfg, batch):
    """batch: dict with 'tokens' (B,S_t) and optionally 'prefix' (B,P,D)."""
    x = embed_apply(params["embed"], batch["tokens"])
    if cfg.input_mode == "tokens+prefix":
        x = jnp.concatenate([batch["prefix"].astype(x.dtype), x], axis=1)
    # keep activations batch-sharded only: without this, the embedding
    # table's FSDP dim leaks a `data`-sharded d_model into the residual
    # stream and GSPMD replicates downstream layers (measured: ~45 GiB)
    return constrain(x, ("dp", "r", "r"))


def _add_counters(a, b):
    # in name order: the traced program, and so its compile-cache key,
    # must not follow the process's string hashing
    return {n: a.get(n, 0) + b.get(n, 0) for n in sorted({*a, *b})}


def forward(params, cfg, batch, lowering="scan", impl="ref", remat=True,
            return_hidden=False, apply_head=True, return_counters=False):
    """Returns (logits, aux_loss[, hidden][, counters]); ``counters`` sums
    each layer's counters (``moe.HELD_PICKS``, ``moe.PICKS``) over the
    layers."""
    x = embed_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    aux_total = jnp.zeros((), jnp.float32)
    counters = {}

    for seg_params, (pattern, repeats) in zip(params["segments"], cfg.segments):
        def body(x, p_r, _pattern=pattern):
            aux, counts = jnp.zeros((), jnp.float32), {}
            for j, kind in enumerate(_pattern):
                x, a, c = layer_apply(p_r[f"p{j}"], kind, x, cfg, positions,
                                      impl)
                aux, counts = aux + a, _add_counters(counts, c)
            return x, (aux, counts)
        if remat:
            body = jax.checkpoint(body)
        # 'unroll' uses scan(unroll=R), NOT a python loop over t[r] slices:
        # indexing stacked layer params drops their PartitionSpec and GSPMD
        # replicates the weights (measured: 13x per-layer FLOPs at qwen2
        # prefill — EXPERIMENTS.md §Perf cycle 2b)
        unroll = repeats if lowering == "unroll" else 1
        x, (auxs, counts) = jax.lax.scan(body, x, seg_params, unroll=unroll)
        aux_total = aux_total + auxs.sum()
        counters = _add_counters(counters, jax.tree.map(jnp.sum, counts))

    h = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = None
    if apply_head:
        logits = lm_head_apply(params["embed"], params.get("head"), h,
                               cfg.tie_embeddings)
        logits = constrain(logits, ("dp", "r", "model"))  # vocab stays sharded
    out = (logits, aux_total)
    if return_hidden:
        out += (h,)
    if return_counters:
        out += (counters,)
    return out


def loss_fn(params, cfg, batch, lowering="scan", impl="ref", remat=True):
    """Next-token LM loss (+aux, +MTP when configured). labels: -1 = ignore.

    The metrics hold the layers' summed ``counters`` (empty where no layer
    counts)."""
    need_h = bool(cfg.mtp_depth)
    out = forward(params, cfg, batch, lowering, impl, remat,
                  return_hidden=need_h, return_counters=True)
    logits, aux = out[0], out[1]
    loss = softmax_xent(logits, batch["labels"])
    metrics = {"lm_loss": loss, "aux_loss": aux, "counters": out[-1]}
    if need_h:
        h = out[2]
        mtp = params["mtp"]
        S = h.shape[1]
        tok_emb = embed_inputs(params, cfg, batch)
        h_in = jnp.concatenate(
            [rmsnorm_apply(mtp["norm_h"], h[:, : S - 1], cfg.norm_eps),
             rmsnorm_apply(mtp["norm_e"], tok_emb[:, 1:], cfg.norm_eps)], -1)
        x2 = jnp.einsum("...i,io->...o", h_in, mtp["proj"]["w"])
        positions = jnp.broadcast_to(
            jnp.arange(S - 1, dtype=jnp.int32), (h.shape[0], S - 1))
        last_kind = cfg.segments[-1][0][-1]
        x2, _, _ = layer_apply(jax.tree.map(lambda t: t[0], mtp["layer"]),
                               last_kind, x2, cfg, positions, impl)
        h2 = rmsnorm_apply(params["final_norm"], x2, cfg.norm_eps)
        logits2 = lm_head_apply(params["embed"], params.get("head"), h2,
                                cfg.tie_embeddings)
        mtp_labels = jnp.concatenate(
            [batch["labels"][:, 2:],
             jnp.full((h.shape[0], 1), -1, batch["labels"].dtype)], axis=1)
        mtp_loss = softmax_xent(logits2, mtp_labels)
        metrics["mtp_loss"] = mtp_loss
        loss = loss + 0.3 * mtp_loss
    total = loss + aux
    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# Decode (serve_step: one token against an existing cache)
# ---------------------------------------------------------------------------
def init_cache(cfg, batch, seq_len, dtype=jnp.bfloat16):
    caches = []
    for pattern, repeats in cfg.segments:
        seg = {}
        for j, kind in enumerate(pattern):
            one = layer_cache_init(kind, cfg, batch, seq_len, dtype)
            seg[f"p{j}"] = jax.tree.map(
                lambda t, _r=repeats: jnp.broadcast_to(t[None], (_r, *t.shape)),
                one)
        caches.append(seg)
    return caches


def decode_step(params, cfg, cache, token, pos, lowering="scan"):
    """token: (B,1) int32; pos: () int32. Returns (logits (B,1,V), new_cache)."""
    x = embed_apply(params["embed"], token)
    new_caches = []
    for seg_params, seg_cache, (pattern, repeats) in zip(
            params["segments"], cache, cfg.segments):
        def body(x, pc, _pattern=pattern):
            p_r, c_r = pc
            nc = {}
            for j, kind in enumerate(_pattern):
                x, nc[f"p{j}"] = layer_decode(p_r[f"p{j}"], kind, x, cfg,
                                              c_r[f"p{j}"], pos)
            return x, nc
        unroll = repeats if lowering == "unroll" else 1
        x, nc = jax.lax.scan(body, x, (seg_params, seg_cache), unroll=unroll)
        new_caches.append(nc)
    h = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head_apply(params["embed"], params.get("head"), h,
                           cfg.tie_embeddings)
    return logits, new_caches


def prefill(params, cfg, batch, lowering="scan", impl="ref"):
    """Full-sequence forward returning last-position logits (cache is
    produced by the per-layer apply fns; for the dry-run the interesting
    artifact is the compute/collective profile, so we return logits only).

    Perf note (§Perf cycle 0, found via the roofline): the LM head is
    applied ONLY to the last position — materializing (B, 32k, 200k)
    logits made the head dominate dense-arch prefill by >10x."""
    _, _, h = forward(params, cfg, batch, lowering, impl, remat=False,
                      return_hidden=True, apply_head=False)
    logits = lm_head_apply(params["embed"], params.get("head"), h[:, -1:],
                           cfg.tie_embeddings)
    return constrain(logits, ("dp", "r", "model"))[:, 0]


def count_params(params):
    return sum(x.size for x in jax.tree.leaves(params))
