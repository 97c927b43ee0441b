"""Model substrate: unified decoder LM (dense/GQA, MoE, Mamba2-SSD, hybrid,
conv/attention with experts)."""

from .config import ModelConfig
from .lm import (init_params, param_axes, forward, forward_with_aux,
                 init_cache, cache_axes, cross_entropy)
from . import layers, mamba2, moe
