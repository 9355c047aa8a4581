"""Architecture configs: one module per assigned architecture.

``get_config(name)`` returns the exact published configuration;
``get_smoke_config(name)`` returns a reduced same-family variant for CPU
smoke tests (small widths/depths/experts, tiny vocab). The full configs are
only ever lowered abstractly (launch/dryrun.py).
"""

from __future__ import annotations

import importlib

from repro.models.config import ArchConfig

ARCHS = (
    "granite-3-8b",
    "qwen1.5-0.5b",
    "granite-8b",
    "deepseek-7b",
    "xlstm-350m",
    "mixtral-8x22b",
    "dbrx-132b",
    "hubert-xlarge",
    "jamba-1.5-large-398b",
    "qwen2-vl-2b",
    "moonlight-16b-a3b",
)

_MODULES = {name: "repro.configs." + name.replace("-", "_").replace(".", "_") for name in ARCHS}


def _module(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {', '.join(ARCHS)}")
    return importlib.import_module(_MODULES[name])


def get_config(name: str) -> ArchConfig:
    cfg = _module(name).config()
    cfg.validate()
    return cfg


def get_smoke_config(name: str) -> ArchConfig:
    cfg = _module(name).smoke_config()
    cfg.validate()
    return cfg
