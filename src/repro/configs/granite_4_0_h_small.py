"""granite-4.0-h-small — hybrid Mamba-2 / NoPE attention with a dropless MoE
FFN on every layer (40L, d=4096; 72 experts top-10, expert width 768, one
shared expert of width 1536; 32B total / 9B active).

Each period of 10 layers puts attention at its 6th layer (layers 5, 15, 25
and 35) and Mamba-2 everywhere else: 128 heads of 64, d_state 128, one
group, a width-4 convolution with bias, chunks of 256. Attention is GQA
32/8 heads of 128 without position embedding (NoPE), scaled by 1/128.
muP multipliers: embeddings x12, each sublayer's output x0.22, logits /16.
The router (no bias) picks 10 of 72 experts by logit and weighs them by a
softmax over the 10 picked. [hf:ibm-granite/granite-4.0-h-small config.json]

``CONFIG`` is the published model. ``RANK0`` is what one chip serves of a
stated deployment: four pipeline stages of 10 layers, each stage's layers
divided over a v5e-4 host's four chips by expert parallelism, 18 experts a
chip. The chip runs layers 0-9 whole except for the experts: the router
keeps its 72 outputs and top-10, and the chip computes experts 0-17 and the
shared expert; what experts 18-71 would add is left out.
"""
import dataclasses

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    rope=False,
    attn_scale=1 / 128,
    num_experts=72,
    experts_per_token=10,
    moe_every=1,
    moe_shared_expert=True,
    expert_d_ff=768,
    shared_expert_d_ff=1536,
    moe_dropless=True,
    attn_every=10,
    attn_offset=5,
    ssm_state=128,
    ssm_headdim=64,  # d_inner = 8192 -> 128 heads
    ssm_expand=2,
    ssm_conv=4,
    ssm_chunk=256,
    rmsnorm_eps=1e-5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    subquadratic=True,  # 4 attention layers in 40 -> long_500k runs
    source="hf:ibm-granite/granite-4.0-h-small",
)

RANK0 = dataclasses.replace(
    CONFIG,
    name="granite-4.0-h-small-ep4-rank0",
    num_layers=10,  # pipeline stage 0 of 4
    experts_held=18,  # experts 0-17 of 72: rank 0 of 4
    expert_offset=0,
)
