"""Architecture registry: ``--arch <id>`` resolves here.

Each ``repro/configs/<id>.py`` module defines ``CONFIG`` (the exact published
configuration) and ``reduced()`` (a smoke-test-sized config of the same
family).  Importing this module populates the registry lazily so that config
files stay single-purpose and greppable.
"""
from __future__ import annotations

import importlib

from repro.config.core import ModelConfig

# id -> module path (one file per assigned architecture + the paper's own four)
_ARCH_MODULES: dict[str, str] = {
    # assigned pool (10)
    "moonshot-v1-16b-a3b": "repro.configs.moonshot_v1_16b_a3b",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "olmo-1b": "repro.configs.olmo_1b",
    "phi4-mini-3.8b": "repro.configs.phi4_mini_3_8b",
    "tinyllama-1.1b": "repro.configs.tinyllama_1_1b",
    "internlm2-20b": "repro.configs.internlm2_20b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
    "jamba-v0.1-52b": "repro.configs.jamba_v0_1_52b",
    "jamba2-3b": "repro.configs.jamba2_3b",
    "phi-3-vision-4.2b": "repro.configs.phi_3_vision_4_2b",
    # the paper's own models (Section 4.1)
    "lstm-ae-f32-d2": "repro.configs.lstm_ae_f32_d2",
    "lstm-ae-f32-d6": "repro.configs.lstm_ae_f32_d6",
    "lstm-ae-f64-d2": "repro.configs.lstm_ae_f64_d2",
    "lstm-ae-f64-d6": "repro.configs.lstm_ae_f64_d6",
}

REGISTRY = dict(_ARCH_MODULES)  # public view of known ids


def _module(arch: str):
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch])


def get_config(arch: str) -> ModelConfig:
    """The exact published configuration for ``arch``."""
    return _module(arch).CONFIG


def reduced_config(arch: str) -> ModelConfig:
    """A smoke-test-sized config of the same family (CPU-runnable)."""
    return _module(arch).reduced()


def list_archs() -> list[str]:
    return sorted(_ARCH_MODULES)
