"""Config system + per-architecture configs (``--arch <id>``)."""
from .base import (
    SHAPES,
    AttentionConfig,
    MambaConfig,
    ModelConfig,
    MoEConfig,
    ParallelConfig,
    ShapeConfig,
    TrainConfig,
    XLSTMConfig,
    YarnConfig,
    with_overrides,
)
from .registry import ARCH_IDS, cells, get_config, get_shape, get_smoke_config

__all__ = [
    "SHAPES",
    "AttentionConfig",
    "MambaConfig",
    "ModelConfig",
    "MoEConfig",
    "ParallelConfig",
    "ShapeConfig",
    "TrainConfig",
    "XLSTMConfig",
    "YarnConfig",
    "with_overrides",
    "ARCH_IDS",
    "cells",
    "get_config",
    "get_shape",
    "get_smoke_config",
]
