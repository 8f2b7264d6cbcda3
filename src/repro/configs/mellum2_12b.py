"""mellum2-12b [hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]

28L d_model=2304 32H (GQA kv=4) head_dim=128 vocab=98304 (untied), a code
model.  Layers repeat (sliding, sliding, sliding, full): sliding-window
attention over 1024 keys, rope theta 500000; full attention with YaRN
(factor 16 over 8192 original positions, beta 32 / 1, attention factor
1.2772588722239782).  Every FFN is sparse: 64 routed experts, top-8,
moe_intermediate_size 896, gates renormalised over the 8 chosen, no shared
expert.  RMSNorm eps 1e-6, no attention bias.
"""
from repro.models.config import ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="mellum2-12b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv=4,
    head_dim=128,
    d_ff=7168,
    vocab=98304,
    mlp="swiglu",
    pattern=("local_moe", "local_moe", "local_moe", "moe"),
    rope_theta=500_000.0,
    yarn=YarnConfig(factor=16.0, original_max_position=8192,
                    beta_fast=32.0, beta_slow=1.0,
                    attention_factor=1.2772588722239782),
    window=1024,
    moe=MoEConfig(num_experts=64, top_k=8, d_expert=896),
)
