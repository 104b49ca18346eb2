"""granite-4.0-h-micro [hf:ibm-granite/granite-4.0-h-micro, config.json] —
hybrid: 40 layers, Mamba-2 mixers with GQA attention at layers 5, 15, 25
and 35 (period ``MMMMMAMMMM``), a SwiGLU MLP in every layer, no position
embedding, muP multipliers on the embedding, both residual branches, the
softmax and the logits."""

from repro.configs.base import ModelConfig, SSMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,             # d_model / num_heads; the config gives none
    d_ff=8192,               # shared_intermediate_size
    vocab_size=100_352,
    hidden_act="silu",
    norm="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=True,
    position_embedding_type="nope",
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, chunk_size=256,
                  conv_width=4, ngroups=1, conv_bias=True),
    layer_types=_PERIOD * 4,
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    source="hf:ibm-granite/granite-4.0-h-micro (config.json)",
)
