"""Assigned-architecture configs (+ shapes)."""
from .base import ArchConfig, ShapeCell, SHAPES, applicable_shapes  # noqa: F401
from .registry import get_config, list_archs, reduced_config  # noqa: F401

# Import config modules so they register themselves.
from . import (  # noqa: F401,E402
    qwen3_32b,
    deepseek_7b,
    granite_34b,
    h2o_danube3_4b,
    moonshot_v1_16b_a3b,
    qwen3_moe_30b_a3b,
    llava_next_mistral_7b,
    mamba2_370m,
    jamba_1_5_large_398b,
    granite_4_0_h_small,
    hubert_xlarge,
)

ALL_ARCHS = list_archs()
