"""Per-architecture smoke tests: reduced configs, one forward + one train
step + one decode step on CPU; asserts shapes and finiteness (deliverable f).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.steps import make_train_step
from repro.models import transformer as tr


def make_batch(cfg, B=2, S=16, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    batch = {"tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    if cfg.input_mode == "tokens+prefix":
        batch["prefix"] = jax.random.normal(
            k1, (B, cfg.prefix_len, cfg.d_model), jnp.float32)
        batch["labels"] = jnp.concatenate(
            [jnp.full((B, cfg.prefix_len), -1, jnp.int32), batch["labels"]], 1)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    assert cfg.citation
    assert sum(len(p) * r for p, r in cfg.segments) == cfg.n_layers


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512 and cfg.n_experts <= 4
    params = tr.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    batch = make_batch(cfg)
    S_total = 16 + (cfg.prefix_len if cfg.input_mode == "tokens+prefix" else 0)

    logits, aux = tr.forward(params, cfg, batch)
    assert logits.shape == (2, S_total, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())

    step = make_train_step(cfg, lr=0.01)
    new_params, loss = jax.jit(step)(params, batch)
    assert bool(jnp.isfinite(loss))
    # params actually changed
    diffs = [float(jnp.abs(a - b).max()) for a, b in
             zip(jax.tree.leaves(params), jax.tree.leaves(new_params))]
    assert max(diffs) > 0
    for t in jax.tree.leaves(new_params):
        assert bool(jnp.isfinite(t).all())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    params = tr.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S_max = 2, 8
    cache = tr.init_cache(cfg, B, S_max, jnp.float32)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, cache2 = tr.decode_step(params, cfg, cache, tok, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "xlstm-1.3b",
                                  "jamba-v0.1-52b", "deepseek-v3-671b",
                                  "moonlight-16b-a3b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode logits == full-sequence forward logits."""
    cfg = get_smoke_config(arch)
    if cfg.mtp_depth:
        cfg = cfg.with_(mtp_depth=0)
    if cfg.n_experts:
        # capacity dropping is batch-size dependent (forward sees B*S tokens,
        # decode sees B) — exact parity needs a drop-free capacity factor
        cfg = cfg.with_(capacity_factor=float(cfg.n_experts))
    params = tr.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    B, S = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    full_logits, _ = tr.forward(params, cfg, {"tokens": toks},
                                lowering="unroll")

    cache = tr.init_cache(cfg, B, S, jnp.float32)
    dec = []
    for t in range(S):
        lg, cache = tr.decode_step(params, cfg, cache, toks[:, t:t + 1],
                                   jnp.int32(t), lowering="unroll")
        dec.append(lg[:, 0])
    dec_logits = jnp.stack(dec, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits), rtol=2e-3, atol=2e-3)


def test_sliding_window_decode_matches_forward():
    """SWA (the long_500k carve-in): windowed forward == ring-buffer decode."""
    cfg = get_smoke_config("internlm2-1.8b").with_(window=4)
    params = tr.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    B, S = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    full_logits, _ = tr.forward(params, cfg, {"tokens": toks},
                                lowering="unroll")
    cache = tr.init_cache(cfg, B, S, jnp.float32)   # ring buffer size = window
    assert cache[0]["p0"]["k"].shape[2] == 4        # (repeats,B,window,KV,hd)
    dec = []
    for t in range(S):
        lg, cache = tr.decode_step(params, cfg, cache, toks[:, t:t + 1],
                                   jnp.int32(t), lowering="unroll")
        dec.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(dec, 1)),
                               np.asarray(full_logits), rtol=2e-3, atol=2e-3)
