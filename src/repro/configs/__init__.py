"""Architecture registry: importing this package registers all configs."""
from repro.configs.base import (  # noqa: F401
    ATTN_GLOBAL, ATTN_LOCAL, RECURRENT, RWKV6,
    EncoderConfig, FrontendConfig, MLAConfig, MoEConfig, ModelConfig,
    SHAPES, ShapeConfig, depth_cut, get_config, list_archs, reduced, register,
    shapes_for,
)

# Assigned architectures (public pool) ------------------------------------
from repro.configs import recurrentgemma_9b  # noqa: F401
from repro.configs import kimi_k2_1t_a32b  # noqa: F401
from repro.configs import arctic_480b  # noqa: F401
from repro.configs import seamless_m4t_medium  # noqa: F401
from repro.configs import granite_8b  # noqa: F401
from repro.configs import qwen2_72b  # noqa: F401
from repro.configs import minitron_4b  # noqa: F401
from repro.configs import gemma2_27b  # noqa: F401
from repro.configs import internvl2_2b  # noqa: F401
from repro.configs import rwkv6_3b  # noqa: F401

# The paper's own evaluation models ---------------------------------------
from repro.configs import llama2_7b  # noqa: F401
from repro.configs import deepseek_v2_lite  # noqa: F401
