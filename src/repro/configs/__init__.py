from repro.configs.base import (  # noqa: F401
    ARCHS, SHAPES, ModelConfig, MoEConfig, ServedCut, SSMConfig, ShapeConfig,
    all_cells, get_config, get_shape, reduced, served_config, served_cut,
)
