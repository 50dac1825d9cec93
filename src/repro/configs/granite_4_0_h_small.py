"""granite-4.0-h-small [hybrid]: 40L d_model=4096, Mamba2 (128 heads of
64, state 128) with one NoPE GQA layer (32H, kv=8, head 128) per 10,
MoE 72 experts top-10 of width 768 plus a shared expert of 1536 on every
layer, vocab=100352, tied  [hf:ibm-granite/granite-4.0-h-small config.json]

Period of 10 layers: Mamba2 x5, attention, Mamba2 x4 (``layer_types``
puts attention at 5, 15, 25, 35).  No position embedding; scores scaled
by ``attention_multiplier`` 1/128; embedding x12, each residual branch
x0.22, logits /16; RMSNorm eps 1e-5.  Router: top-10 of 72 logits, then
a softmax over those 10.  Total params ~= 32.2B, active ~= 9B.
"""
from .base import ArchConfig
from .registry import register


@register
def granite_4_0_h_small() -> ArchConfig:
    return ArchConfig(
        name="granite-4.0-h-small",
        family="hybrid",
        n_layers=40,
        d_model=4096,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=768,  # per-expert hidden
        shared_d_ff=1536,
        vocab_size=100352,
        tie_embeddings=True,
        rope_theta=None,  # position_embedding_type "nope"
        attn_scale=0.0078125,
        norm_eps=1e-5,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        n_experts=72,
        top_k=10,
        attn_period=10,
        moe_period=1,
        ssm_state=128,
        ssm_head_dim=64,
        ssm_expand=2,
        ssm_conv=4,
        ssm_chunk=256,
    )
