from repro.configs.base import (  # noqa: F401
    INPUT_SHAPES, SUBQUADRATIC, FFConfig, InputShape, MLAConfig, ModelConfig,
    MoEConfig, RGLRUConfig, SSMConfig, YaRNConfig, get_config, list_configs, register)
