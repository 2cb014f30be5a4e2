from repro.configs.base import (
    ARCH_IDS,
    SHAPES,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    RopeScaling,
    ShapeConfig,
    all_cells,
    get_config,
    get_smoke_config,
)
from repro.configs.moses import DEFAULT as MOSES_DEFAULT
from repro.configs.moses import CostModelConfig, MosesConfig

__all__ = [
    "ARCH_IDS",
    "SHAPES",
    "MLAConfig",
    "ModelConfig",
    "MoEConfig",
    "RopeScaling",
    "ShapeConfig",
    "all_cells",
    "get_config",
    "get_smoke_config",
    "MOSES_DEFAULT",
    "CostModelConfig",
    "MosesConfig",
]
