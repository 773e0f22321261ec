"""granite-4.0-h-small — 40 layers in periods of ten (nine Mamba-2 mixers
and one NoPE GQA attention mixer, each followed by a 72-expert top-10 MoE
FFN plus a shared expert), muP multipliers, tied embeddings.
[hf:ibm-granite/granite-4.0-h-small; hf]

``expert_share`` is one chip's share of an 8-way expert-parallel
deployment: experts 0-8 of the 72 are held here; the router keeps its 72
outputs and its top-10."""
from repro.configs.base import ModelConfig

_PERIOD = ("mamba_moe",) * 5 + ("attn_moe",) + ("mamba_moe",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, d_head=128,
    d_ff=768, vocab_size=100352, tie_embeddings=True,
    n_experts=72, top_k=10, shared_expert_ff=1536, expert_share=(0, 9),
    ssm_state=128, ssm_expand=2, ssm_head_dim=64, ssm_conv_width=4,
    ssm_conv_bias=True, layer_pattern=_PERIOD,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0, use_rope=False,
    norm_eps=1e-5,
    source="[hf:ibm-granite/granite-4.0-h-small; hf]",
)
