"""Config registry: ``get_arch("<id>")`` / ``--arch <id>`` on all launchers."""
from __future__ import annotations

from typing import Dict

from repro.configs.base import (ALL_SHAPES, ArchConfig, OptimizerConfig,
                                RunConfig, ShapeSpec, shapes_for,
                                TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)

from repro.configs.chameleon_34b import CONFIG as _chameleon
from repro.configs.granite_8b import CONFIG as _granite
from repro.configs.yi_34b import CONFIG as _yi
from repro.configs.stablelm_3b import CONFIG as _stablelm
from repro.configs.glm4_9b import CONFIG as _glm4
from repro.configs.deepseek_v2_236b import CONFIG as _deepseek
from repro.configs.kimi_k2_1t import CONFIG as _kimi
from repro.configs.kimi_vl_a3b import CONFIG as _kimi_vl
from repro.configs.xlstm_350m import CONFIG as _xlstm
from repro.configs.whisper_base import CONFIG as _whisper
from repro.configs.recurrentgemma_2b import CONFIG as _rgemma

ARCHS: Dict[str, ArchConfig] = {
    c.name: c for c in (
        _chameleon, _granite, _yi, _stablelm, _glm4,
        _deepseek, _kimi, _kimi_vl, _xlstm, _whisper, _rgemma,
    )
}


def get_arch(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]


def get_shape(name: str) -> ShapeSpec:
    for s in ALL_SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)
