"""Per-architecture configs (assigned pool) + reduced smoke variants.

``get_config(name)`` / ``get_smoke_config(name)`` / ``ARCHS``.
"""

from importlib import import_module
from typing import Dict, List

from ..config import ModelConfig

ARCHS: List[str] = [
    "zamba2-2.7b",
    "internlm2-20b",
    "deepseek-7b",
    "qwen3-0.6b",
    "qwen3-8b",
    "whisper-base",
    "rwkv6-7b",
    "internvl2-2b",
    "mixtral-8x7b",
    "granite-moe-1b-a400m",
    "granite-4.0-h-small",
]

_MODULES: Dict[str, str] = {a: a.replace("-", "_").replace(".", "_")
                            for a in ARCHS}


def _mod(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; available: {ARCHS}")
    return import_module(f"repro.configs.{_MODULES[name]}")


def get_config(name: str) -> ModelConfig:
    return _mod(name).CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    return _mod(name).SMOKE
