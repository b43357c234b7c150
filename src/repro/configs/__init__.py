"""Architecture registry. ``get_config(arch_id)`` returns the full pool config."""
from __future__ import annotations

from repro.configs.base import (AttentionConfig, DistConfig, INPUT_SHAPES,
                                LayerSpec, ModelConfig, MoEConfig,
                                RecurrentConfig, ShapeConfig)

_REGISTRY = {}


def register(name):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(arch: str) -> ModelConfig:
    _load_all()
    key = arch.replace("_", "-")
    if key not in _REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]()


PRESETS = ("cpu", "chip", "pod")
# decoder layers of the 'chip' preset: the depth at which m=4 agents'
# training state (params, grads, moments and the round's temporaries)
# fits one 16 GB v5e chip at published widths
CHIP_LAYERS = 1


def preset_config(cfg: ModelConfig, preset: str) -> ModelConfig:
    """The config the launchers run under ``--preset``: 'pod' is the
    published config; 'chip' keeps every published width and cuts depth
    to CHIP_LAYERS (training and serving agree on it, so a merged model
    saved by one loads in the other); 'cpu' also cuts widths, for tests
    on the CPU."""
    if preset == "cpu":
        return cfg.reduced(d_model=128, layers=2, vocab=256)
    if preset == "chip":
        return cfg.depth_cut(CHIP_LAYERS)
    if preset != "pod":
        raise ValueError(f"unknown preset {preset!r}; known: {PRESETS}")
    return cfg


def list_archs():
    _load_all()
    return sorted(_REGISTRY)


def _load_all():
    from repro.configs import (arctic_480b, deepseek_v3_671b, gemma_2b,  # noqa: F401
                               olmo_1b, phi3_mini_3_8b, qwen2_vl_72b,
                               recurrentgemma_2b, seamless_m4t_medium,
                               xlstm_1_3b, yi_34b)


__all__ = ["get_config", "list_archs", "preset_config", "PRESETS",
           "CHIP_LAYERS", "register", "ModelConfig", "ShapeConfig",
           "INPUT_SHAPES", "AttentionConfig", "MoEConfig", "RecurrentConfig",
           "LayerSpec", "DistConfig"]
