"""Model configuration schema shared by all assigned architectures."""

from __future__ import annotations

import dataclasses

#: the gated short convolution's causal taps (LFM2's ``conv_L_cache``)
CONV_TAPS = 3


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | vlm | audio
                                 # | conv_moe
    n_layers: int
    d_model: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # flavor
    mlp: str = "swiglu"          # swiglu | geglu
    qk_norm: bool = False
    rope_theta: float = 1e4
    m_rope: bool = False         # sectioned multimodal RoPE (qwen2-vl)
    causal: bool = True
    tie_embeddings: bool = False
    rmsnorm_eps: float = 1e-5

    # MoE
    n_experts: int = 0
    n_experts_active: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    router: str = "softmax"      # softmax | sigmoid_bias (selection by
                                 # sigmoid + expert bias, gates by sigmoid)
    n_experts_held: int = 0      # experts [0, held) live here; 0 = all

    # per-layer operator pattern (conv_moe): "attn" | "conv" per layer; the
    # first ``n_dense_layers`` take the dense MLP, the rest the experts
    layer_pattern: tuple = ()
    n_dense_layers: int = 0

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    ssm_conv_width: int = 4
    ssm_groups: int = 1

    # hybrid layout: shared attention block applied after every k SSM layers
    shared_attn_every: int = 0

    # modality frontend ("none" = token ids; "embed" = precomputed
    # frame/patch embeddings supplied by input_specs — the assignment's stub)
    frontend: str = "none"

    # numerics / parallelism profile
    dtype: str = "bfloat16"
    param_dtype: str = "float32"       # master params ("float32"|"bfloat16")
    opt_moment_dtype: str = "float32"  # Adam moments ("float32"|"int8")
    remat: str = "full"                # full | dots | none
    # perf knobs (hillclimb levers; defaults = paper-faithful baseline)
    attn_softmax_dtype: str = "float32"   # "float32" | "bfloat16"
    attn_blocked_threshold: int = 8192    # seq len above which the flash-
                                          # style blocked kernel is used
    moe_parallelism: str = "tp"           # "tp" (hidden-dim) | "ep" (experts)
    gather_params_once: bool = False      # hoist FSDP all-gathers out of the
                                          # microbatch loop (ZeRO-2-style)
    kv_two_tier: bool = False             # decode: frozen seq-sharded main
                                          # cache + small replicated append
                                          # buffer (kills the per-layer
                                          # masked-select cache rewrite)
    kv_recent_len: int = 128              # append-buffer slots
    # attention-free archs can run 0.5M-token shapes; full-attention skip
    supports_long_context: bool = False

    def __post_init__(self):
        # a pattern read from JSON arrives as a list
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))

    # ---------------------------------------------------------------- derived
    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    def layer_period(self) -> tuple:
        """(length, repeats) of the repeating unit of ``layer_pattern``
        after the dense layers: of the units that repeat at least twice in
        a row from the start, the one that covers most layers (the shortest
        on a tie); where none repeats, all of them, once.  Layers past the
        last whole repeat run unstacked."""
        rest = self.layer_pattern[self.n_dense_layers:]

        def repeats(p):
            n = 0
            while (n + 1) * p <= len(rest) and \
                    rest[n * p:(n + 1) * p] == rest[:p]:
                n += 1
            return n
        best, cover = (len(rest), 1 if rest else 0), 0
        for p in range(1, len(rest) // 2 + 1):
            n = repeats(p)
            if n >= 2 and n * p > cover:
                best, cover = (p, n), n * p
        return best

    def is_attn_layer(self, i: int) -> bool:
        if self.layer_pattern:
            return self.layer_pattern[i] == "attn"
        if self.family in ("ssm",):
            return False
        if self.family == "hybrid":
            return self.shared_attn_every > 0 and \
                (i + 1) % self.shared_attn_every == 0
        return True

    def is_ssm_layer(self, i: int) -> bool:
        return self.family == "ssm" or self.family == "hybrid"

    # --------------------------------------------------------- param counts
    def embed_params(self) -> int:
        n = self.vocab_size * self.d_model
        if not self.tie_embeddings:
            n *= 2
        return n

    def attn_params_per_layer(self) -> int:
        return (self.d_model * self.q_dim          # Wq
                + 2 * self.d_model * self.kv_dim   # Wk, Wv
                + self.q_dim * self.d_model)       # Wo

    def _router_params(self) -> int:
        bias = self.n_experts if self.router == "sigmoid_bias" else 0
        return self.d_model * self.n_experts + bias

    def _has_experts(self, i: int) -> bool:
        """Whether layer ``i`` (from the end where negative) has experts."""
        return self.family in ("moe", "conv_moe") and self.n_experts > 0 \
            and i % self.n_layers >= self.n_dense_layers

    def mlp_params_per_layer(self, i: int = -1) -> int:
        if self._has_experts(i):
            per_e = 3 * self.d_model * self.d_ff_expert
            return (self.experts_held + self.n_shared_experts) * per_e \
                + self._router_params()
        return 3 * self.d_ff * self.d_model            # swiglu/geglu

    def mlp_active_params_per_layer(self, i: int = -1) -> int:
        if self._has_experts(i):
            per_e = 3 * self.d_model * self.d_ff_expert
            return (self.n_experts_active + self.n_shared_experts) * per_e \
                + self._router_params()
        return self.mlp_params_per_layer(i)

    def conv_params_per_layer(self) -> int:
        d = self.d_model
        return d * 3 * d + CONV_TAPS * d + d * d  # w_in, conv, w_out

    def op_params_per_layer(self, i: int) -> int:
        if self.is_attn_layer(i):
            return self.attn_params_per_layer()
        return self.conv_params_per_layer()

    def ssm_params_per_layer(self) -> int:
        di, ds, nh = self.d_inner, self.ssm_state, self.ssm_heads
        g = self.ssm_groups
        in_proj = self.d_model * (2 * di + 2 * g * ds + nh)
        conv = self.ssm_conv_width * (di + 2 * g * ds)
        out_proj = di * self.d_model
        return in_proj + conv + out_proj + 3 * nh      # A, dt_bias, D

    def params_per_layer(self, i: int) -> int:
        if self.family == "ssm":
            return self.ssm_params_per_layer()
        if self.family == "hybrid":
            return self.ssm_params_per_layer()         # shared attn counted once
        return self.op_params_per_layer(i) + self.mlp_params_per_layer(i)

    @property
    def n_params(self) -> int:
        total = self.embed_params()
        total += sum(self.params_per_layer(i) for i in range(self.n_layers))
        if self.family == "hybrid" and self.shared_attn_every:
            # one shared transformer block (attn + mlp), weights shared
            total += self.attn_params_per_layer() + 3 * self.d_ff * self.d_model
        return total

    @property
    def n_active_params(self) -> int:
        if self.family not in ("moe", "conv_moe"):
            return self.n_params
        total = self.embed_params()
        total += sum(self.op_params_per_layer(i)
                     + self.mlp_active_params_per_layer(i)
                     for i in range(self.n_layers))
        return total
