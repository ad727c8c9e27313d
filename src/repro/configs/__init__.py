"""Architecture config registry: one module per assigned architecture.

Usage:
    from repro import configs
    cfg = configs.get("qwen3-14b")           # full (assignment) config
    cfg = configs.get("qwen3-14b", reduced=True)   # smoke-test config
    configs.ARCH_IDS                          # all ids
"""
from __future__ import annotations

import importlib

ARCH_IDS = [
    "granite-3-8b",
    "yi-9b",
    "qwen3-14b",
    "llama3.2-3b",
    "whisper-large-v3",
    "qwen3-moe-30b-a3b",
    "phi3.5-moe-42b-a6.6b",
    "mamba2-780m",
    "phi-3-vision-4.2b",
    "jamba-v0.1-52b",
    "moonlight-16b-a3b",
]

_MODULES = {
    "granite-3-8b": "granite_3_8b",
    "yi-9b": "yi_9b",
    "qwen3-14b": "qwen3_14b",
    "llama3.2-3b": "llama3_2_3b",
    "whisper-large-v3": "whisper_large_v3",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe_42b_a6_6b",
    "mamba2-780m": "mamba2_780m",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}


def get(arch_id: str, reduced: bool = False):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.REDUCED if reduced else mod.CONFIG
