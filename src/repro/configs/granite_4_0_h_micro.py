"""granite-4.0-h-micro [hybrid] — dense Mamba-2 / NoPE GQA hybrid, 3B
[hf:ibm-granite/granite-4.0-h-micro, config.json, model_type
granitemoehybrid].

40 layers in four periods of ten: index 5 of each period is attention
(layers 5, 15, 25, 35), the other nine Mamba-2. Every mixer is followed by
a gated-SiLU MLP (``shared_intermediate_size`` 8192; no experts). Tied
embeddings, NoPE, and the four granite multipliers.
"""
from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,                 # GQA
    head_dim=64,
    d_ff=8192,                      # shared_intermediate_size
    vocab_size=100352,
    tie_embeddings=True,
    norm_eps=1e-5,
    # mamba_n_heads 64 x mamba_d_head 64 = 4096 = mamba_expand 2 x 2048
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, n_groups=1,
                  conv_width=4, chunk_size=256),
    attn_period=10,
    attn_index=5,
    rope=False,                     # position_embedding_type "nope"
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    max_seq_len=131072,
    source="hf:ibm-granite/granite-4.0-h-micro",
)


def one_period() -> ModelConfig:
    """Layers 0-9, one whole period at the published widths: the depth
    one chip holds as one of four pipeline stages."""
    return CONFIG.replace(num_layers=CONFIG.attn_period)


def smoke_config() -> ModelConfig:
    """The same layer pattern (one period, attention at index 5) and
    multipliers at CPU widths."""
    return CONFIG.replace(
        name="granite-4.0-h-smoke", num_layers=10, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        ssm=SSMConfig(d_state=16, head_dim=16, expand=2, n_groups=1,
                      conv_width=4, chunk_size=16))
