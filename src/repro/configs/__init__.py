from repro.configs.base import (
    InputShape,
    MLAConfig,
    MMDiTConfig,
    MoEConfig,
    ModelConfig,
    SHAPES,
    UNetConfig,
)
from repro.configs.registry import ARCHS, get_config, get_smoke_config, list_archs

__all__ = [
    "ARCHS",
    "InputShape",
    "MLAConfig",
    "MMDiTConfig",
    "MoEConfig",
    "ModelConfig",
    "SHAPES",
    "UNetConfig",
    "get_config",
    "get_smoke_config",
    "list_archs",
]
