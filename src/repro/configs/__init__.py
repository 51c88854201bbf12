"""Config registry: ``get_config(arch_id)`` / ``list_archs()``.

Selectable via ``--arch <id>`` in the launchers (repro.launch.*).
"""
from __future__ import annotations

from typing import Dict, List

from repro.configs.base import (ArchConfig, InputShape, INPUT_SHAPES,
                                get_shape, reduce_for_smoke)

from repro.configs.zamba2_1p2b import CONFIG as _zamba2
from repro.configs.zamba2_7b import CONFIG as _zamba2_7b
from repro.configs.codeqwen1p5_7b import CONFIG as _codeqwen
from repro.configs.gemma2_2b import CONFIG as _gemma2
from repro.configs.deepseek_v3_671b import CONFIG as _deepseek
from repro.configs.minitron_8b import CONFIG as _minitron
from repro.configs.internvl2_76b import CONFIG as _internvl
from repro.configs.whisper_large_v3 import CONFIG as _whisper
from repro.configs.granite_moe_1b import CONFIG as _granite
from repro.configs.qwen1p5_110b import CONFIG as _qwen110
from repro.configs.mamba2_130m import CONFIG as _mamba2

_REGISTRY: Dict[str, ArchConfig] = {
    c.arch_id: c
    for c in (_zamba2, _codeqwen, _gemma2, _deepseek, _minitron,
              _internvl, _whisper, _granite, _qwen110, _mamba2,
              _zamba2_7b)
}


def get_config(arch_id: str) -> ArchConfig:
    if arch_id not in _REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[arch_id]


def list_archs() -> List[str]:
    return sorted(_REGISTRY)


__all__ = ["ArchConfig", "InputShape", "INPUT_SHAPES", "get_config",
           "get_shape", "list_archs", "reduce_for_smoke"]
