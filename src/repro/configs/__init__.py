"""Config registry: one module per assigned architecture (+ paper workloads).

Each module defines CONFIG (the exact assigned configuration) and SMOKE (a
reduced same-family config for CPU smoke tests). Use ``get_config(name)`` /
``get_smoke(name)`` / ``ARCH_NAMES``.
"""
from __future__ import annotations

import importlib

ARCH_NAMES = [
    "internvl2-26b",
    "qwen3-1.7b",
    "qwen2-1.5b",
    "gemma3-12b",
    "nemotron-4-340b",
    "llama4-maverick-400b-a17b",
    "llama4-scout-17b-a16e",
    "zamba2-1.2b",
    "musicgen-large",
    "rwkv6-7b",
    "qwen3-next-80b-a3b",
]

_MODULES = {n: "repro.configs." + n.replace("-", "_").replace(".", "_")
            for n in ARCH_NAMES}


def _load(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_NAMES}")
    return importlib.import_module(_MODULES[name])


def get_config(name: str):
    return _load(name).CONFIG


def get_smoke(name: str):
    return _load(name).SMOKE
