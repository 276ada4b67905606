"""Architecture registry: ``--arch <id>`` resolution.

Each module in ``repro.configs`` registers a full-size config (the exact
published architecture) and a reduced config (same family, tiny dims) used
by CPU smoke tests.  Full configs are only ever lowered via ShapeDtypeStructs
in the dry-run — they are never materialized on the host.
"""
from __future__ import annotations

import importlib
from typing import Callable, Dict, Tuple

from .base import ModelConfig

_FULL: Dict[str, Callable[[], ModelConfig]] = {}
_REDUCED: Dict[str, Callable[[], ModelConfig]] = {}

# Modules in repro.configs providing register() side effects.
_CONFIG_MODULES = (
    "deepseek_v2_lite_16b",
    "qwen2_moe_a2p7b",
    "xlstm_350m",
    "jamba_v0_1_52b",
    "whisper_small",
    "qwen2_vl_72b",
    "granite_34b",
    "gemma3_12b",
    "llama3_8b",
    "yi_9b",
    "neuralut_hdr_5l",
    "neuralut_jsc_2l",
    "neuralut_jsc_5l",
    "polylut_add_jsc_2l",
    "polylut_add_jsc_5l",
    "polylut_add_jsc_xl",
    "lm_100m",
)

_loaded = False


def register(name: str, full: Callable[[], ModelConfig],
             reduced: Callable[[], ModelConfig]) -> None:
    _FULL[name] = full
    _REDUCED[name] = reduced


def _ensure_loaded() -> None:
    global _loaded
    if _loaded:
        return
    for mod in _CONFIG_MODULES:
        importlib.import_module(f"repro.configs.{mod}")
    _loaded = True


def list_archs() -> Tuple[str, ...]:
    _ensure_loaded()
    return tuple(sorted(_FULL))


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    _ensure_loaded()
    table = _REDUCED if reduced else _FULL
    if name not in table:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(table)}")
    return table[name]()
