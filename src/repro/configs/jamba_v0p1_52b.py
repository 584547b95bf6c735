"""Jamba v0.1 52B — hybrid Mamba + attention (1:7) with MoE.

[arXiv:2403.19887] 32 layers, d_model=4096, 32 heads (GQA kv=8),
d_ff=14336, vocab=65536.  Attention every 8th layer (offset 4); MoE
(16 experts, top-2) every other layer (offset 1).  Mamba: d_state=16,
d_conv=4, expand=2.  Hybrid -> runs long_500k natively.  Each token's
top 2 experts are weighted by their softmax scores over all 16, not
renormalised (``JambaSparseMoeBlock.forward`` in Hugging Face
transformers' ``models/jamba/modeling_jamba.py``: softmax, top-k, no
division).
"""
from repro.configs.base import ModelConfig, MoEConfig, MambaConfig

CONFIG = ModelConfig(
    name="jamba-v0.1-52b",
    family="hybrid",
    num_layers=32,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    vocab_size=65536,
    attn_layer_period=8,
    attn_layer_offset=4,
    ssm_kind="mamba",
    mamba=MambaConfig(d_state=16, d_conv=4, expand=2),
    moe=MoEConfig(
        num_experts=16,
        top_k=2,
        num_shared_experts=0,
        d_expert=14336,
        moe_layer_period=2,
        moe_layer_offset=1,
        norm_topk_prob=False,
    ),
)
