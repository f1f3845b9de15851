"""A small configuration and traffic for the tests: the cells' code paths
at sizes a CPU runs in seconds. Leaves of a few hundred thousand elements
keep the int8 wire's rounding (one quantum where the program and the
reference disagree in the last bit) as small against a leaf's change as
it is at the cells' sizes."""
from __future__ import annotations

import copy

CONFIG = {
    "name": "tiny", "model_type": "internlm2", "hidden_act": "silu",
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "tie_word_embeddings": False,
}

TRAFFIC = {
    "placement": "vmap", "participants": 2, "batch_size": 2, "seq_len": 16,
    "steps_per_epoch": 2, "t0": 1, "n_examples": 32, "codec": "fused",
    "codec_bits": 8, "aggregator": "full", "sync_policy": "fle",
}


def config(**kw):
    out = copy.deepcopy(CONFIG)
    out.update(kw)
    return out


def traffic(**kw):
    from bench import spec
    raw = copy.deepcopy(TRAFFIC)
    raw.update(kw)
    return spec.complete_traffic(raw)
