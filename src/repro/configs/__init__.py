"""Config registry: ``get_config(arch_id)`` / ``get_smoke_config(arch_id)``,
and ``cut_depth`` for a chip's share of a published config."""
from __future__ import annotations

import importlib

from repro.configs.base import (CoLearnConfig, InputShape, INPUT_SHAPES,
                                ModelConfig, TrainConfig)

_MODULES = {
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "qwen1.5-32b": "qwen1_5_32b",
    "musicgen-large": "musicgen_large",
    "arctic-480b": "arctic_480b",
    "internvl2-76b": "internvl2_76b",
    "xlstm-1.3b": "xlstm_1_3b",
    "qwen2-72b": "qwen2_72b",
    "internlm2-1.8b": "internlm2_1_8b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

ARCH_IDS = tuple(_MODULES)


def _mod(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")


def get_config(arch_id: str) -> ModelConfig:
    return _mod(arch_id).CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    return _mod(arch_id).smoke_config()


def cut_depth(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """``cfg`` with only its first ``n_layers`` layers; every width stays.

    Whole repeats of each segment's pattern are kept in order, and a
    pattern cut midway keeps its leading layers as one extra segment.
    """
    if not 0 < n_layers <= cfg.n_layers:
        raise ValueError(f"n_layers must be in [1, {cfg.n_layers}] for "
                         f"{cfg.name}; got {n_layers}")
    segments, left = [], n_layers
    for pattern, repeats in cfg.segments:
        whole = min(repeats, left // len(pattern))
        if whole:
            segments.append((pattern, whole))
            left -= whole * len(pattern)
        if left and whole < repeats:
            segments.append((pattern[:left], 1))
            left = 0
        if not left:
            break
    return cfg.with_(n_layers=n_layers, segments=tuple(segments))
