"""Configuration system for the repro framework.

Two config families:

* :class:`ModelConfig` — the assigned large-model architectures
  (dense / moe / ssm / hybrid / vlm / audio).  These are exercised at
  full scale only through the multi-pod dry-run (ShapeDtypeStruct, no
  allocation) and at reduced scale through smoke tests.

* :class:`PaperNetConfig` — the paper's own Table-1 networks (DNNs and
  small CNNs) used by the figure-for-figure benchmarks.

Everything is a frozen dataclass: hashable, usable as a jit static arg.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


# --------------------------------------------------------------------------
# Sub-configs
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Fine-grained mixture-of-experts (DeepSeekMoE-style)."""
    num_experts: int                 # routed experts
    top_k: int
    num_shared_experts: int = 0      # always-on shared experts
    d_expert: int = 0                # intermediate dim of EACH expert
    moe_layer_period: int = 1        # every n-th layer is MoE
    moe_layer_offset: int = 0
    first_dense_layers: int = 0      # leading layers that use a dense FFN
    dense_d_ff: int = 0              # FFN dim of those dense layers
    router_aux_coef: float = 0.001   # load-balance loss coefficient
    capacity_factor: float = 1.25    # per-expert buffer slack
    # "softmax" (Switch/GShard) or "sigmoid" (DeepSeek-V3: sigmoid scores,
    # selection biased by a non-gradient balance term, weights normalised
    # over the selected experts)
    router_type: str = "softmax"
    # softmax router: renormalise the top-k scores to sum to 1 before
    # weighting the experts.  Every softmax-routed config here sets it
    # false, as published (each routed expert weighted by its softmax
    # score over all experts); the default keeps the renormalising
    # router for configs built without the key (bench/model.py's)
    norm_topk_prob: bool = True


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2                  # d_inner = expand * d_model


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora: int = 64             # LoRA rank for data-dependent decay
    mix_lora: int = 32               # LoRA rank for token-shift mixing


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2/V3)."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


# --------------------------------------------------------------------------
# Main model config
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads

    # ---- attention flavour -------------------------------------------------
    attention: str = "gqa"           # gqa | mla | none (attn-free)
    qkv_bias: bool = False
    qk_norm: bool = False
    swa_window: int = 0              # 0 = full attention; >0 = sliding window
    # §Perf: pad query heads to this count with structurally-zero heads
    # (zero wq/wo slices + output mask => mathematically exact) so the
    # head axis divides the model axis.  0 = off.
    pad_heads_to: int = 0
    rope_theta: float = 10_000.0
    mla: Optional[MLAConfig] = None

    # ---- hybrid / ssm ------------------------------------------------------
    # every `attn_layer_period`-th layer (at `attn_layer_offset`) is attention,
    # the rest are `ssm_kind` layers.  attn_layer_period=1 -> all attention,
    # attn_layer_period=0 -> attention-free.
    attn_layer_period: int = 1
    attn_layer_offset: int = 0
    ssm_kind: str = "none"           # mamba | rwkv6 | none
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None

    # ---- MoE ---------------------------------------------------------------
    moe: Optional[MoEConfig] = None

    # ---- encoder-decoder (audio) -------------------------------------------
    is_encoder_decoder: bool = False
    encoder_layers: int = 0

    # ---- modality frontend stub (vlm / audio) ------------------------------
    frontend: str = "none"           # none | vision | audio
    num_frontend_tokens: int = 0     # image-patch / mel-frame embeddings

    # ---- serving -----------------------------------------------------------
    # paged decode attention implementation: "xla" (paged_read gather +
    # masked softmax — the reference oracle) or "pallas" (fused
    # page-table-gather + online-softmax kernel, kernels/paged_decode.py;
    # interpret-mode on CPU).  Greedy outputs are pinned equal.
    decode_kernel: str = "xla"

    # ---- extras ------------------------------------------------------------
    mtp_depth: int = 0               # DeepSeek-V3 multi-token prediction heads
    mlp_gated: bool = True           # SwiGLU (3 mats) vs plain 2-mat MLP
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: str = "bfloat16"          # activation/compute dtype
    param_dtype: str = "float32"     # master weights

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # ------------------------------------------------------------------
    # layer-kind pattern (drives scan-over-layers model assembly)
    # ------------------------------------------------------------------
    def mixer_kind(self, layer_idx: int) -> str:
        """'attn' | 'mamba' | 'rwkv6' for a given layer index."""
        if self.attn_layer_period == 0:
            return self.ssm_kind
        if self.attn_layer_period == 1:
            return "attn"
        if layer_idx % self.attn_layer_period == self.attn_layer_offset:
            return "attn"
        return self.ssm_kind

    def ffn_kind(self, layer_idx: int) -> str:
        """'mlp' | 'moe' for a given layer index."""
        m = self.moe
        if m is None:
            return "mlp"
        if layer_idx < m.first_dense_layers:
            return "mlp"
        if layer_idx % m.moe_layer_period == m.moe_layer_offset % m.moe_layer_period:
            return "moe"
        return "mlp"

    def layer_pattern(self) -> Tuple[Tuple[str, str], ...]:
        """Per-layer (mixer, ffn) kinds for the whole (decoder) stack."""
        return tuple(
            (self.mixer_kind(i), self.ffn_kind(i)) for i in range(self.num_layers)
        )

    def block_structure(self) -> Tuple[Tuple[Tuple[str, str], ...], Tuple[Tuple[str, str], ...], int]:
        """Split layers into (unrolled prefix, repeating super-block, n_repeats).

        The repeating super-block is scanned with jax.lax.scan so the HLO
        contains ONE copy of the block body regardless of depth — essential
        for compiling 60+ layer models under SPMD partitioning on CPU.
        """
        pat = self.layer_pattern()
        n = len(pat)
        # prefix = leading layers that break the periodic pattern
        prefix_len = 0
        if self.moe is not None and self.moe.first_dense_layers:
            prefix_len = self.moe.first_dense_layers
        body = pat[prefix_len:]
        # find the shortest period of the body pattern
        period = len(body)
        for cand in range(1, len(body) + 1):
            if len(body) % cand:
                continue
            if body == body[:cand] * (len(body) // cand):
                period = cand
                break
        return pat[:prefix_len], body[:period], len(body) // period

    # ------------------------------------------------------------------
    # parameter counting (for roofline MODEL_FLOPS and memory estimates)
    # ------------------------------------------------------------------
    def attn_params(self) -> int:
        d = self.d_model
        if self.attention == "mla":
            m = self.mla
            qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
            p = d * m.q_lora_rank + m.q_lora_rank * self.num_heads * qk_hd
            p += d * (m.kv_lora_rank + m.qk_rope_head_dim)
            p += m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
            p += self.num_heads * m.v_head_dim * d
            return p
        hd = self.head_dim
        p = d * self.num_heads * hd            # q
        p += 2 * d * self.num_kv_heads * hd    # k, v
        p += self.num_heads * hd * d           # o
        if self.qkv_bias:
            p += (self.num_heads + 2 * self.num_kv_heads) * hd
        return p

    def mamba_params(self) -> int:
        mc = self.mamba or MambaConfig()
        d_in = mc.expand * self.d_model
        p = self.d_model * 2 * d_in                      # in_proj (x, z)
        p += d_in * mc.d_conv                            # conv1d
        p += d_in * (mc.d_state * 2 + d_in // 16)        # B, C, dt projections
        p += d_in * mc.d_state                           # A
        p += d_in * self.d_model                         # out_proj
        return p

    def rwkv_params(self) -> int:
        rc = self.rwkv or RWKVConfig()
        d = self.d_model
        p = 4 * d * d                                    # r, k, v, o (time-mix)
        p += d * d                                       # gate
        p += 2 * (d * rc.decay_lora + rc.decay_lora * d) # decay lora + u
        p += 5 * (d * rc.mix_lora + rc.mix_lora * d)     # token-shift loras
        p += 2 * d * self.d_ff                           # channel-mix (r,k)
        return p

    @property
    def _mlp_mats(self) -> int:
        return 3 if self.mlp_gated else 2

    def ffn_params(self, kind: str) -> int:
        d = self.d_model
        if kind == "mlp":
            return self._mlp_mats * d * self.d_ff
        m = self.moe
        per_exp = self._mlp_mats * d * m.d_expert
        return (m.num_experts + m.num_shared_experts) * per_exp + d * m.num_experts

    def ffn_active_params(self, kind: str) -> int:
        d = self.d_model
        if kind == "mlp":
            return self._mlp_mats * d * self.d_ff
        m = self.moe
        per_exp = self._mlp_mats * d * m.d_expert
        return (m.top_k + m.num_shared_experts) * per_exp + d * m.num_experts

    def _mixer_params(self, kind: str) -> int:
        return {"attn": self.attn_params(),
                "mamba": self.mamba_params(),
                "rwkv6": self.rwkv_params()}[kind]

    def param_count(self, active_only: bool = False) -> int:
        """Total (or activated) parameter count for MODEL_FLOPS = 6·N·D."""
        total = 2 * self.vocab_size * self.d_model       # embed + unembed
        if self.tie_embeddings:
            total -= self.vocab_size * self.d_model
        ffn_p = self.ffn_active_params if active_only else self.ffn_params
        for (mixer, ffn) in self.layer_pattern():
            if mixer == "attn":
                total += self.attn_params()
            elif mixer == "mamba":
                total += self.mamba_params()
            elif mixer == "rwkv6":
                # rwkv block includes its own channel-mix ffn
                total += self.rwkv_params()
                continue
            total += ffn_p(ffn)
        if self.is_encoder_decoder:
            for _ in range(self.encoder_layers):
                total += self.attn_params() + ffn_p("mlp")
            # decoder cross-attention
            total += self.num_layers * self.attn_params()
        return total

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# Paper Table-1 networks
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PaperNetConfig:
    """A network from Table 1 of Vishnu et al. 2016."""
    name: str
    kind: str                        # dnn | cnn
    layer_sizes: Tuple[int, ...] = ()        # dnn: in-hidden...-out
    # cnn fields (paper: 5x5 conv, stride 1, relu, 2x2 maxpool, sigmoid fc)
    image_hw: Tuple[int, int] = (0, 0)
    image_channels: int = 0
    conv_channels: Tuple[int, ...] = ()
    fc_size: int = 0
    num_classes: int = 0
    dataset: str = ""


# --------------------------------------------------------------------------
# Input shapes (assigned)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str                        # train | prefill | decode


TRAIN_4K = InputShape("train_4k", 4_096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32_768, 128, "decode")
LONG_500K = InputShape("long_500k", 524_288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}
