"""Architecture configs (``--arch <id>``). Importing this package registers
all assigned architectures plus the paper's reference model."""
from repro.configs.base import ModelConfig, LayerSpec, ScanGroup, REGISTRY, at_depth, get_config, reduced, register
from repro.configs.shapes import SHAPES, ShapeConfig, get_shape, cells, LONG_CONTEXT_OK, LONG_CONTEXT_SKIP

# register all architectures
from repro.configs.mamba2_2p7b import MAMBA2_2P7B
from repro.configs.deepseek_7b import DEEPSEEK_7B
from repro.configs.gemma_2b import GEMMA_2B
from repro.configs.qwen3_8b import QWEN3_8B
from repro.configs.gemma2_27b import GEMMA2_27B
from repro.configs.mixtral_8x22b import MIXTRAL_8X22B
from repro.configs.deepseek_v2_lite_16b import DEEPSEEK_V2_LITE
from repro.configs.musicgen_large import MUSICGEN_LARGE
from repro.configs.hymba_1p5b import HYMBA_1P5B
from repro.configs.internvl2_76b import INTERNVL2_76B
from repro.configs.llama2_70b import LLAMA2_70B

ASSIGNED_ARCHS = (
    "mamba2-2.7b",
    "deepseek-7b",
    "gemma-2b",
    "qwen3-8b",
    "gemma2-27b",
    "mixtral-8x22b",
    "deepseek-v2-lite-16b",
    "musicgen-large",
    "hymba-1.5b",
    "internvl2-76b",
)

__all__ = [
    "ModelConfig", "LayerSpec", "ScanGroup", "REGISTRY", "at_depth", "get_config",
    "reduced", "register", "SHAPES", "ShapeConfig", "get_shape", "cells",
    "LONG_CONTEXT_OK", "LONG_CONTEXT_SKIP", "ASSIGNED_ARCHS",
]
