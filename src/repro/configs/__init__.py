"""Config registry: importing this package registers all architectures."""
from repro.configs import (  # noqa: F401
    arctic_480b,
    internvl2_1b,
    jamba_1_5_large_398b,
    llama3_2_1b,
    mixtral_8x7b,
    moonshot_v1_16b_a3b,
    qwen2_0_5b,
    qwen2_1_5b,
    whisper_tiny,
    xlstm_350m,
)
from repro.configs.agilenn_cifar import AgileNNConfig  # noqa: F401
from repro.configs.base import (  # noqa: F401
    ArchConfig,
    InputShape,
    MLASpec,
    MoESpec,
    get_config,
    list_configs,
)
from repro.configs.shapes import SHAPES, get_shape  # noqa: F401

ASSIGNED_ARCHS = [
    "internvl2-1b",
    "moonshot-v1-16b-a3b",
    "qwen2-1.5b",
    "xlstm-350m",
    "jamba-1.5-large-398b",
    "arctic-480b",
    "qwen2-0.5b",
    "llama3.2-1b",
    "whisper-tiny",
    "mixtral-8x7b",
]
