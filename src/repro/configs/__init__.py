from repro.configs.base import (  # noqa: F401
    ArchConfig, GNNConfig, LMConfig, MoEConfig, RecsysConfig,
    ShapeConfig, LM_SHAPES, GNN_SHAPES, REC_SHAPES,
)
from repro.configs.registry import get_arch, ASSIGNED_ARCHS  # noqa: F401
