"""Assigned architecture configs (one module per arch).

Importing this package registers every config with the model registry.
"""
from repro.configs import (  # noqa: F401
    gemma3_27b,
    internlm2_20b,
    kimi_k2_1t_a32b,
    llama4_maverick_400b_a17b,
    minitron_8b,
    paligemma_3b,
    qwen1_5_32b,
    whisper_large_v3,
    xlstm_125m,
    zamba2_1_2b,
)
