"""Architecture registry: one module per assigned architecture."""

from repro.configs.base import (  # noqa: F401
    ModelConfig, ShapeConfig, SHAPES, ARCHS, register, get_config,
    smoke_config, list_archs, cells,
)

# import for registration side effects
from repro.configs import (  # noqa: F401, E402
    whisper_tiny, mistral_large_123b, nemotron_4_340b, stablelm_1_6b,
    deepseek_7b, xlstm_1_3b, llava_next_34b, granite_moe_3b_a800m,
    dbrx_132b, zamba2_7b, lulesh_dash, picolm, deepseek_v2_lite,
)
