from repro.configs.base import (
    SHAPES, SMOKE_SHAPE, ArchConfig, ShapeConfig, all_archs, get_arch,
    smoke_config,
)

__all__ = [
    "SHAPES", "SMOKE_SHAPE", "ArchConfig", "ShapeConfig", "all_archs",
    "get_arch", "smoke_config",
]
