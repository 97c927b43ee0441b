"""lfm2-8b-a1b — gated short convolutions beside GQA attention, 32 experts
top-4 with a biased sigmoid router [hf:LiquidAI/LFM2-8B-A1B]."""
from repro.models.config import ModelConfig

C, A = "conv", "attn"

CONFIG = ModelConfig(
    name="lfm2-8b-a1b", family="conv_moe",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=7168, vocab_size=65536, mlp="swiglu", qk_norm=True,
    rope_theta=1e6, tie_embeddings=True, rmsnorm_eps=1e-5,
    n_experts=32, n_experts_active=4, d_ff_expert=1792,
    router="sigmoid_bias",
    layer_pattern=(C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, C,
                   A, C, C, A, C, C),
    n_dense_layers=2,
)
