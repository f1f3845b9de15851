"""The expert layer's held share and latent attention's two forms.

* MLA: the expanded training path equals the absorbed form over several
  attention chunks, with and without a query low rank, and prefill then
  decode through the latent cache equals the full forward.
* MoE: the grouped product's kernel (megablox, interpreted) equals its
  reference with gradients and under ``vmap``; the shares of a layer's
  experts, with the shared experts counted once, add up to the uncut
  layer; routing skewed onto one expert drops no token; the routing bias
  takes no gradient step; the held-pick counter reaches the round's log.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import mla, moe
from repro.models import transformer as tr

S, CHUNK = 64, 16


def mla_cfg(q_lora_rank=0):
    return get_smoke_config("moonlight-16b-a3b").with_(
        q_lora_rank=q_lora_rank)


def _absorbed_prefill(p, x, cfg, positions, chunk):
    """The absorbed form over a whole sequence, from the helpers decode
    uses: every head attends over one latent key and value."""
    q_all, c_kv, k_rope = mla._latents(p, x, cfg, positions)
    kv = jnp.concatenate([c_kv, k_rope], -1)[:, :, None]
    o = mla.chunked_attention(q_all, kv, c_kv[:, :, None], n_kv_heads=1,
                              softmax_scale=mla._scale(cfg), chunk_q=chunk,
                              chunk_kv=chunk)
    return mla._out_proj(p, o, cfg)


@pytest.mark.parametrize("q_lora_rank", [0, 24])
def test_expanded_matches_absorbed_prefill(monkeypatch, q_lora_rank):
    cfg = mla_cfg(q_lora_rank)
    monkeypatch.setattr(mla, "chunked_attention", functools.partial(
        mla.chunked_attention, chunk_q=CHUNK, chunk_kv=CHUNK))
    p = mla.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert ("w_q" in p) == (q_lora_rank == 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    with jax.default_matmul_precision("highest"):
        want = _absorbed_prefill(p, x, cfg, pos, CHUNK)
        got, _ = mla.mla_apply(p, x, cfg, pos)
    assert S // CHUNK >= 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_prefill_then_decode_through_the_latent_cache():
    """Prefill the first half in the expanded form, then decode the rest
    token by token in the absorbed form: the logits equal the full
    forward's."""
    cfg = mla_cfg()
    params = tr.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    B, n = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, n), 0,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        full, _ = tr.forward(params, cfg, {"tokens": toks})
        cache = tr.init_cache(cfg, B, n, jnp.float32)
        for t in range(n):
            lg, cache = tr.decode_step(params, cfg, cache, toks[:, t:t + 1],
                                       jnp.int32(t))
            np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                       np.asarray(full[:, t]),
                                       rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------
def _dense_grouped(lhs, rhs, sizes):
    ends = jnp.cumsum(sizes)
    gid = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = jnp.einsum("mk,mkn->mn", lhs, rhs[jnp.minimum(gid, len(sizes) - 1)],
                     precision="highest")
    return jnp.where((gid < len(sizes))[:, None], out, 0.0)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_grouped_matmul_matches_dense(impl):
    """Forward, both gradients and the vmap of the grouped product against
    a per-row dense product, over the rows the groups hold."""
    m, k, n, g = 40, 64, 24, 3
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    sizes = jnp.array([[7, 0, 20], [11, 13, 2]], jnp.int32)
    lhs = jax.random.normal(keys[0], (2, m, k))
    rhs = jax.random.normal(keys[1], (2, g, k, n))
    dout = jax.random.normal(keys[2], (2, m, n))
    rows = jnp.arange(m)[None, :, None] < sizes.sum(1)[:, None, None]

    def run(fn):
        def one(a, b, s, c):
            out, vjp = jax.vjp(lambda a, b: fn(a, b, s), a, b)
            return (out,) + vjp(c * (jnp.arange(m) < s.sum())[:, None])
        return jax.vmap(one)(lhs, rhs, sizes, dout)

    got = run(lambda a, b, s: moe.grouped_matmul(a, b, s, impl))
    with jax.default_matmul_precision("highest"):
        want = run(_dense_grouped)
    np.testing.assert_allclose(np.where(rows, got[0], 0), want[0],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.where(rows, got[1], 0), want[1],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[2], want[2], rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# the held share
# ---------------------------------------------------------------------------
def moe_cfg(top_k=3):
    return get_smoke_config("moonlight-16b-a3b").with_(
        n_experts=8, top_k=top_k, experts_held=2)


def _uncut(p, x, cfg):
    """Every expert of the layer run over every token and weighted by its
    gate (0 where not chosen), plus the shared experts: the plain layer."""
    T = x.shape[0] * x.shape[1]
    xt = x.reshape(T, -1)
    top_i, top_p, _ = moe.route(p, xt, cfg, x.shape[0])
    gate = jnp.zeros((T, cfg.n_experts)).at[
        jnp.arange(T)[:, None], top_i].add(top_p)
    h = jnp.einsum("td,edf->etf", xt, p["wi"])
    g = jnp.einsum("td,edf->etf", xt, p["wg"])
    out = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * h, p["wo"])
    y = jnp.einsum("te,etd->td", gate, out)
    s = p["shared"]
    y = y + moe._swiglu(xt, s["wi"], s["wg"], s["wo"], jnp.einsum)
    return y.reshape(x.shape)


def _layer(cfg, seed=0):
    full = cfg.with_(experts_held=cfg.n_experts)
    p = moe.moe_init(jax.random.PRNGKey(seed), full, jnp.float32)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9),
                                                (cfg.n_experts,))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, cfg.d_model))
    return full, p, x


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each: each share's routed part (its
    output less the shared experts) summed, plus the shared experts once,
    equals the uncut layer; so does the share that holds every expert."""
    cfg = moe_cfg()
    full, p, x = _layer(cfg)
    H, E = cfg.experts_held, cfg.n_experts
    with jax.default_matmul_precision("highest"):
        want = _uncut(p, x, full)
        sh = p["shared"]
        base = moe._swiglu(x.reshape(-1, cfg.d_model), sh["wi"], sh["wg"],
                           sh["wo"], jnp.einsum).reshape(x.shape)
        total = base
        for s in range(E // H):
            held = jnp.roll(jnp.arange(E), -s * H)
            share = dict(p, router=p["router"][:, held],
                         router_bias=p["router_bias"][held],
                         wi=p["wi"][held[:H]], wg=p["wg"][held[:H]],
                         wo=p["wo"][held[:H]])
            y, _, _ = moe.moe_apply(share, x, cfg)
            total = total + (y - base)
        whole, _, counters = moe.moe_apply(p, x, full)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert int(counters[moe.HELD_PICKS]) == int(counters[moe.PICKS]) \
        == 2 * 16 * cfg.top_k


def test_no_token_is_dropped_under_skewed_routing():
    """A bias that sends every token to experts 0 and 1, both held here:
    every token's whole routed part is computed, with no capacity."""
    cfg = moe_cfg(top_k=2)
    full, p, x = _layer(cfg)
    p = dict(p, router_bias=jnp.zeros(cfg.n_experts).at[:2].set(100.0))
    held = dict(p, wi=p["wi"][:2], wg=p["wg"][:2], wo=p["wo"][:2])
    with jax.default_matmul_precision("highest"):
        got, _, counters = moe.moe_apply(held, x, cfg)
        want = _uncut(p, x, full)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert int(counters[moe.HELD_PICKS]) == 2 * 16 * 2


def test_the_routing_bias_takes_no_gradient():
    cfg = moe_cfg()
    full, p, x = _layer(cfg)
    held = dict(p, wi=p["wi"][:2], wg=p["wg"][:2], wo=p["wo"][:2])

    def loss(q):
        y, aux, _ = moe.moe_apply(q, x, cfg)
        return jnp.sum(y ** 2) + aux
    g = jax.grad(loss)(held)
    assert not np.asarray(g["router_bias"]).any()
    assert np.asarray(g["router"]).any() and np.asarray(g["wi"]).any()


@pytest.mark.parametrize("chunk", [32, 1])
def test_held_picks_reach_the_round_log(chunk):
    """The fused engine sums the counter on the device over the round's
    steps, layers and participants, and brings it back with the round, in
    one executable or in chunks: in its log entry and in the round's
    record of ``analysis.spans``."""
    from repro.analysis import spans
    from repro.configs.base import CoLearnConfig
    from repro.core.colearn import CoLearner
    cfg = get_smoke_config("moonlight-16b-a3b")
    K, steps, B, T = 2, 2, 2, 8

    def loss_fn(params, batch):
        x, y = batch
        return tr.loss_fn(params, cfg, {"tokens": x, "labels": y})

    learner = CoLearner.from_flags(
        CoLearnConfig(n_participants=K, T0=2, epochs_rule="fle"), loss_fn,
        engine="fused", fused_chunk=chunk)
    state = learner.init(tr.init_params(jax.random.PRNGKey(0), cfg,
                                        jnp.float32))
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (K, steps, B, T)).astype(np.int32)
    state = learner.run_round(state, lambda i, j: (data, data))
    log = state["log"][-1]
    picks = 2 * K * steps * B * T * cfg.top_k      # 2 epochs, one layer
    assert log.counters[moe.PICKS] == picks
    assert 0 < log.counters[moe.HELD_PICKS] < picks
    assert spans.recent()[-1].counters == log.counters
