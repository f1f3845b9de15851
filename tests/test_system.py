"""End-to-end behaviour tests: the paper's protocol on a real (tiny) model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, cut_depth, get_config, get_smoke_config
from repro.configs.base import CoLearnConfig
from repro.core.colearn import CoLearner
from repro.core.compression import make_compress_fn
from repro.data.partition import partition_arrays
from repro.data.pipeline import ParticipantData
from repro.data.synthetic import lm_examples
from repro.models import transformer as tr


def setup(K=3, seq=32, n=240, arch="internlm2-1.8b", seed=0):
    cfg = get_smoke_config(arch).with_(n_layers=1, segments=((("gqa:dense",), 1),))
    x, y = lm_examples(seed, n, seq, cfg.vocab_size)
    shards = partition_arrays([x, y], K, seed)
    data = ParticipantData(shards, batch_size=8, seed=seed)

    def loss_fn(params, batch):
        bx, by = batch
        return tr.loss_fn(params, cfg, {"tokens": bx, "labels": by})

    params = tr.init_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    return cfg, data, loss_fn, params


def run_colearn(rounds=3, compress=None, **kw):
    cfg, data, loss_fn, params = setup(**kw)
    ccfg = CoLearnConfig(n_participants=3, T0=1, eta0=0.05, epsilon=1e-6,
                         max_rounds=rounds)
    learner = CoLearner.from_flags(ccfg, loss_fn, compress_fn=compress)
    state = learner.init(params)
    for _ in range(rounds):
        state = learner.run_round(
            state, lambda i, j: tuple(map(jnp.asarray,
                                          data.epoch_batches(i, j))))
    return learner, state


def test_colearn_trains_tiny_transformer():
    learner, state = run_colearn(rounds=3)
    losses = [np.mean(l.local_losses) for l in state["log"]]
    assert losses[-1] < losses[0] - 0.1, losses
    # Eq.2 bookkeeping: comm volume == 2 x model bytes each round
    one = learner.param_bytes(state)
    assert state["log"][0].comm_bytes == 2 * one


def test_colearn_participants_share_model_after_round():
    _, state = run_colearn(rounds=1)
    for t in jax.tree.leaves(state["params"]):
        np.testing.assert_allclose(t[0], t[-1], rtol=1e-6)


def test_compressed_averaging_close_to_exact():
    """Beyond-paper int8 upload: same trajectory within quantization noise."""
    _, s_exact = run_colearn(rounds=2)
    _, s_comp = run_colearn(rounds=2, compress=make_compress_fn())
    l_exact = np.mean(s_exact["log"][-1].local_losses)
    l_comp = np.mean(s_comp["log"][-1].local_losses)
    assert abs(l_exact - l_comp) < 0.1 * max(abs(l_exact), 1e-3) + 0.05


def test_train_driver_cli_runs():
    from repro.launch.train import main
    rc = main(["--arch", "internlm2-1.8b", "--participants", "2",
               "--rounds", "2", "--t0", "1", "--n-examples", "64",
               "--batch-size", "4", "--seq-len", "16",
               "--steps-per-epoch", "2"])
    assert rc == 0


def test_train_driver_builds_published_widths(monkeypatch):
    """--widths published --n-layers N loads the published config with its
    depth cut; the run stops at parameter init (full widths are too large
    for a CPU test)."""
    from repro.launch import train

    class Built(Exception):
        pass

    def stop(key, cfg, dtype):
        raise Built(cfg)

    monkeypatch.setattr(train.tr, "init_params", stop)
    with pytest.raises(Built) as e:
        train.main(["--arch", "internlm2-1.8b", "--widths", "published",
                    "--n-layers", "1", "--participants", "2",
                    "--n-examples", "16", "--batch-size", "4",
                    "--seq-len", "16"])
    cfg = e.value.args[0]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (1, 2048, 16, 8, 8192, 92_544)
    assert cfg.layer_kinds() == ["gqa:dense"]


def test_train_driver_rejects_depth_beyond_config():
    from repro.launch.train import main
    with pytest.raises(SystemExit) as e:
        main(["--n-layers", "3"])                 # the smoke config has 2
    assert e.value.code == 2


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cut_depth_keeps_widths_and_layer_order(arch):
    full = get_config(arch)
    for n in (1, 2, 3):
        cut = cut_depth(full, n)
        assert cut.layer_kinds() == full.layer_kinds()[:n]
        assert cut.with_(n_layers=full.n_layers,
                         segments=full.segments) == full


def test_train_driver_cli_rejects_codec_plus_compress():
    from repro.launch.train import main
    with pytest.raises(SystemExit) as e:
        main(["--codec", "exact", "--compress", "fused"])
    assert e.value.code == 2


def test_serve_driver_cli_runs():
    from repro.launch.serve import main
    rc = main(["--arch", "xlstm-1.3b", "--batch", "2", "--prompt-len", "4",
               "--new-tokens", "4", "--max-seq", "16"])
    assert rc == 0
