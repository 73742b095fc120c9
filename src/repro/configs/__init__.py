"""Architecture registry: the 10 assigned architectures, the paper's own
logistic-regression workload and the benchmark's DeepSeek-V2-Lite,
selectable via ``--arch <id>``."""
from __future__ import annotations

import importlib

from .base import ModelConfig

_MODULES = {
    "internvl2-26b": "internvl2_26b",
    "qwen2-72b": "qwen2_72b",
    "qwen3-8b": "qwen3_8b",
    "whisper-tiny": "whisper_tiny",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "grok-1-314b": "grok_1_314b",
    "xlstm-350m": "xlstm_350m",
    "zamba2-1.2b": "zamba2_1p2b",
    "qwen3-1.7b": "qwen3_1p7b",
    "granite-34b": "granite_34b",
    "logistic-paper": "logistic_paper",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

# the assigned architectures; deepseek-v2-lite trains only (no serving path)
ARCHS = [a for a in _MODULES if a not in ("logistic-paper",
                                          "deepseek-v2-lite")]


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(_MODULES)}")
    mod = importlib.import_module(f".{_MODULES[name]}", __package__)
    return mod.CONFIG


def list_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in _MODULES}


__all__ = ["ModelConfig", "ARCHS", "get_config", "list_configs"]
