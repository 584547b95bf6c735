"""Config registry: `get_config(arch_id)` + reduced smoke variants."""
from __future__ import annotations

import dataclasses

from repro.configs.base import (
    ModelConfig, MoEConfig, MambaConfig, RWKVConfig, MLAConfig,
    PaperNetConfig, InputShape, INPUT_SHAPES,
    TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K,
)
from repro.configs import (
    rwkv6_1p6b, deepseek_coder_33b, deepseek_moe_16b, deepseek_v3_671b,
    llava_next_mistral_7b, granite_20b, jamba_v0p1_52b, qwen2p5_32b,
    qwen3_1p7b, seamless_m4t_large_v2,
)
from repro.configs.paper_nets import PAPER_NETS

ARCHITECTURES = {
    m.CONFIG.name: m.CONFIG
    for m in (
        rwkv6_1p6b, deepseek_coder_33b, deepseek_moe_16b, deepseek_v3_671b,
        llava_next_mistral_7b, granite_20b, jamba_v0p1_52b, qwen2p5_32b,
        qwen3_1p7b, seamless_m4t_large_v2,
    )
}

# Archs that must NOT lower long_500k at all (documented skip in DESIGN.md §4)
LONG_500K_SKIPS = {"seamless-m4t-large-v2"}
# Dense/full-attention archs that get the sliding-window variant for long_500k
SWA_FOR_LONG = {
    "deepseek-coder-33b", "granite-20b", "qwen2.5-32b", "qwen3-1.7b",
    "deepseek-moe-16b", "deepseek-v3-671b", "llava-next-mistral-7b",
}
SWA_WINDOW = 8192


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCHITECTURES:
        raise KeyError(
            f"unknown arch {arch!r}; available: {sorted(ARCHITECTURES)}")
    return ARCHITECTURES[arch]


def config_for_shape(arch: str, shape_name: str) -> ModelConfig:
    """Arch config adjusted for an input shape (SWA for long_500k)."""
    cfg = get_config(arch)
    if shape_name == "long_500k":
        if arch in LONG_500K_SKIPS:
            raise ValueError(f"{arch} skips long_500k (DESIGN.md §4)")
        if arch in SWA_FOR_LONG:
            cfg = cfg.with_overrides(swa_window=SWA_WINDOW)
    return cfg


def smoke_config(arch: str) -> ModelConfig:
    """Reduced variant of the same family: d_model<=512, <=4 experts, and
    the fewest layers (2 or more) in which the scanned super-block runs
    twice, so per-layer indexing into stacked params and caches is
    exercised.

    Keeps every structural feature (GQA ratio, MLA, MoE shared/routed,
    hybrid interleave, enc-dec, frontend stub) at toy scale for CPU tests.
    """
    cfg = get_config(arch)
    kw = dict(
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=max(1, min(cfg.num_kv_heads, 4) * 4 // max(cfg.num_heads, 1)) or 1,
        head_dim=64,
        d_ff=512,
        vocab_size=512,
    )
    # keep the GQA ratio where possible
    ratio = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
    kw["num_kv_heads"] = max(1, 4 // min(ratio, 4))
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            num_experts=4,
            top_k=min(cfg.moe.top_k, 2),
            num_shared_experts=min(cfg.moe.num_shared_experts, 1),
            d_expert=128,
            first_dense_layers=min(cfg.moe.first_dense_layers, 1),
            dense_d_ff=512,
            # generous capacity: smoke tests assert exact path equality;
            # capacity-drop semantics are tested separately
            capacity_factor=8.0,
        )
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(
            q_lora_rank=64, kv_lora_rank=32,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
        kw["head_dim"] = 48
    if cfg.rwkv is not None:
        kw["rwkv"] = RWKVConfig(head_dim=32, decay_lora=16, mix_lora=8)
        kw["num_heads"] = 8   # 256 / 32
        kw["num_kv_heads"] = 8
        kw["head_dim"] = 32
    if cfg.ssm_kind == "mamba":
        kw["mamba"] = MambaConfig(d_state=8, d_conv=4, expand=2)
        # keep 1:7-style interleave but fit in 2 layers: attn at layer 1
        kw["attn_layer_period"] = 2
        kw["attn_layer_offset"] = 1
    if cfg.is_encoder_decoder:
        kw["encoder_layers"] = 2
    if cfg.frontend == "vision":
        kw["num_frontend_tokens"] = 16
    cfg = cfg.with_overrides(**kw)
    prefix, pattern, n_rep = cfg.block_structure()
    if n_rep < 2:
        cfg = cfg.with_overrides(num_layers=len(prefix) + 2 * len(pattern))
    return cfg


__all__ = [
    "ModelConfig", "MoEConfig", "MambaConfig", "RWKVConfig", "MLAConfig",
    "PaperNetConfig", "InputShape", "INPUT_SHAPES",
    "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "ARCHITECTURES", "PAPER_NETS", "LONG_500K_SKIPS", "SWA_FOR_LONG",
    "get_config", "config_for_shape", "smoke_config",
]
