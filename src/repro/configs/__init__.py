"""Architecture registry: importing this package registers all configs."""
from repro.configs.base import ArchConfig, get_config, list_archs  # noqa: F401

from repro.configs import (  # noqa: F401
    gemma3_27b, granite_34b, stablelm_3b, qwen3_32b, deepseek_v2_236b,
    moonlight_16b_a3b, recurrentgemma_2b, mamba2_1p3b,
    llama32_vision_11b, musicgen_medium,
)
