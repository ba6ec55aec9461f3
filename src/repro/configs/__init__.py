"""Architecture registry: ``--arch <id>`` resolution for every driver.

get_config(id)  / get_smoke_config(id)  / list_archs().
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ModelConfig

_MODULES: Dict[str, str] = {
    "phi-3-vision-4.2b": "repro.configs.phi_3_vision_4_2b",
    "qwen2.5-32b": "repro.configs.qwen2_5_32b",
    "minicpm3-4b": "repro.configs.minicpm3_4b",
    "hubert-xlarge": "repro.configs.hubert_xlarge",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
    "mamba2-1.3b": "repro.configs.mamba2_1_3b",
    "qwen3-32b": "repro.configs.qwen3_32b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "qwen1.5-0.5b": "repro.configs.qwen1_5_0_5b",
}


def list_archs() -> List[str]:
    return sorted(_MODULES.keys())


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; available: {list_archs()}")
    return importlib.import_module(_MODULES[arch]).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; available: {list_archs()}")
    return importlib.import_module(_MODULES[arch]).SMOKE
