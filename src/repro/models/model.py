"""Top-level models.

* ``init_model`` / ``apply_model`` — the assigned large architectures
  (decoder-only, encoder-decoder, VLM with stubbed frontends).
* ``init_paper_net`` / ``apply_paper_net`` — the paper's Table-1 DNNs and
  CNNs (5x5 conv / ReLU / 2x2 max-pool / sigmoid FC / softmax out).

``apply_model(cfg, params, batch, mode=..., cache=..., cache_pos=...)``
returns ``{"logits", "cache", "aux", "mtp_logits"?}``.

Batch formats:
  decoder-only : {"tokens": (B,S)}
  vlm          : {"tokens": (B,S_text), "vision_embeds": (B,N_img,D_vis)}
  audio encdec : {"src_embeds": (B,S_src,d_model), "tgt_tokens": (B,S_tgt)}
  decode       : {"tokens": (B,1)} + cache/cache_pos
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.layers import (
    init_embed, apply_embed, init_rmsnorm, rmsnorm, dense_init,
    truncated_normal, scoped)
from repro.sharding.ctx import constrain_bsd, constrain_logits

VISION_EMBED_DIM = 1024      # CLIP ViT-L/14-336 output width (stubbed)


def _encoder_cfg(cfg):
    return cfg.with_overrides(num_layers=cfg.encoder_layers,
                              is_encoder_decoder=False,
                              attn_layer_period=1, ssm_kind="none",
                              moe=None)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_model(cfg, key):
    ks = jax.random.split(key, 8)
    params = {"embed": init_embed(ks[0], cfg.vocab_size, cfg.d_model)}
    if cfg.frontend == "vision":
        params["vision_proj"] = {
            "w1": dense_init(ks[1], VISION_EMBED_DIM, cfg.d_model),
            "b1": jnp.zeros((cfg.d_model,), jnp.float32),
            "w2": dense_init(ks[2], cfg.d_model, cfg.d_model),
            "b2": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    if cfg.is_encoder_decoder:
        params["encoder"] = tfm.init_stack(_encoder_cfg(cfg), ks[3])
        params["enc_norm"] = init_rmsnorm(cfg.d_model)
    params["decoder"] = tfm.init_stack(cfg, ks[4],
                                       cross=cfg.is_encoder_decoder)
    params["final_norm"] = init_rmsnorm(cfg.d_model)
    if not cfg.tie_embeddings:
        params["unembed"] = {"table": truncated_normal(
            ks[5], (cfg.vocab_size, cfg.d_model), 0.02)}
    if cfg.mtp_depth > 0:
        dense_ff = cfg.moe.dense_d_ff if cfg.moe else cfg.d_ff
        params["mtp"] = {
            "norm_h": init_rmsnorm(cfg.d_model),
            "norm_e": init_rmsnorm(cfg.d_model),
            "proj": dense_init(ks[6], 2 * cfg.d_model, cfg.d_model),
            "block": tfm.init_layer(cfg, ks[7], ("attn", "mlp"),
                                    dense_ff=dense_ff),
        }
    return params


def init_cache(cfg, batch, max_len, dtype, *, cross_len=0, pool=None):
    """pool=(num_pages, page_size): build the *paged* serving cache —
    attention/MLA K/V live in shared token-major page pools sized by
    the pool, not by batch×max_len; per-slot recurrent SSM states keep
    the (batch,) axis.  Decode then reads through a
    ``serve.kvcache``-managed page table (``apply_model(paged=...)``)."""
    return tfm.init_stack_cache(cfg, batch, max_len, dtype,
                                cross=cfg.is_encoder_decoder,
                                cross_len=cross_len, pool=pool)


# --------------------------------------------------------------------------
# apply
# --------------------------------------------------------------------------

@scoped("lm_head")
def _logits(cfg, params, x):
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["unembed"]["table"])
    logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                        table.astype(jnp.float32))
    return constrain_logits(logits)


def _vision_proj(params, v, dt):
    p = params["vision_proj"]
    h = v.astype(dt) @ p["w1"].astype(dt) + p["b1"].astype(dt)
    return jax.nn.gelu(h) @ p["w2"].astype(dt) + p["b2"].astype(dt)


def apply_model(cfg, params, batch, *, mode="train", cache=None,
                cache_pos=None, remat=False, last_only=False, paged=None):
    dt = jnp.dtype(cfg.dtype)
    aux = jnp.zeros((), jnp.float32)
    if paged is not None and mode != "decode":
        raise ValueError("paged KV cache reads are decode-mode only "
                         "(chunked prefill runs as decode)")

    # ---------- encoder (audio frontend stub feeds src_embeds) ----------
    enc_out = None
    if cfg.is_encoder_decoder and "src_embeds" in batch:
        src = batch["src_embeds"].astype(dt)
        pos_e = jnp.arange(src.shape[1])
        enc_cfg = _encoder_cfg(cfg)
        enc, _, a = tfm.apply_stack(enc_cfg, params["encoder"], src,
                                    positions=pos_e, mode="train",
                                    causal=False, remat=remat)
        enc_out = rmsnorm(params["enc_norm"], enc, cfg.norm_eps)
        aux = aux + a

    # ---------- decoder input sequence ----------
    tokens = batch.get("tgt_tokens", batch.get("tokens"))
    with jax.named_scope("embed"):
        x = apply_embed(params["embed"], tokens, dt)
        if cfg.frontend == "vision" and "vision_embeds" in batch:
            vis = _vision_proj(params, batch["vision_embeds"], dt)
            x = jnp.concatenate([vis, x], axis=1)
        x = constrain_bsd(x)

    S = x.shape[1]
    if mode == "decode":
        # scalar cache_pos: all slots at the same depth (lockstep slab
        # path, positions (S,)); per-slot (B,) vector: continuous
        # batching, positions (B, S) — paged reads only
        cache_pos = jnp.asarray(cache_pos)
        if cache_pos.ndim == 1:
            if paged is None:
                raise ValueError("per-slot cache_pos needs a paged cache "
                                 "(pass paged=PagedView(...))")
            positions = cache_pos[:, None] + jnp.arange(S)[None]
        else:
            positions = cache_pos + jnp.arange(S)
    else:
        positions = jnp.arange(S)

    x, new_cache, a = tfm.apply_stack(
        cfg, params["decoder"], x, positions=positions, mode=mode,
        cache=cache, cache_pos=cache_pos, enc_out=enc_out, causal=True,
        remat=remat, paged=paged)
    aux = aux + a

    if last_only:
        # serving: only the last position's logits are needed — slice
        # before the unembed matmul (saves S x the logits compute and
        # the (B, S, V) fp32 buffer)
        x = x[:, -1:]
    out = {"logits": _logits(cfg, params, x), "cache": new_cache,
           "aux": aux, "hidden": x}

    # ---------- multi-token prediction head (train only) ----------
    if cfg.mtp_depth > 0 and mode == "train":
        p = params["mtp"]
        # combine hidden at position i with embedding of token i+1
        h = rmsnorm(p["norm_h"], x[:, :-1], cfg.norm_eps)
        e = rmsnorm(p["norm_e"],
                    apply_embed(params["embed"], tokens[:, 1:], dt),
                    cfg.norm_eps)
        hm = jnp.concatenate([h, e], axis=-1) @ p["proj"].astype(dt)
        pos_m = jnp.arange(hm.shape[1])
        hm, _, _ = tfm.apply_layer(cfg, ("attn", "mlp"), p["block"], hm,
                                   positions=pos_m, mode="train")
        out["mtp_logits"] = _logits(cfg, params, hm)
    return out


# --------------------------------------------------------------------------
# MTP drafting (speculative decode)
# --------------------------------------------------------------------------

def _mtp_self_attention(cfg, p, x, dt):
    """The MTP block's attention for a *window-1* (self-only) query.

    Decode-mode drafting feeds the block one position at a time, and the
    only key that position can see is itself: the softmax over a single
    key is identically 1, so the attention output IS the value at the
    query's own position — the q/k projections, qk-norm and RoPE all
    cancel exactly.  That reduction lets the draft head run with no KV
    pool, no page table and no positions, for both GQA and MLA layers.
    Draft quality only moves the acceptance rate; the verify forward
    keeps greedy outputs lossless regardless.
    """
    from repro.models.attention import _padded_heads  # local: avoid cycle
    if cfg.attention == "mla":
        m = cfg.mla
        dkv = x @ p["w_dkv"].astype(dt)
        ckv = rmsnorm(p["kv_norm"], dkv[..., :m.kv_lora_rank], cfg.norm_eps)
        out = jnp.einsum("bsr,rhv->bshv", ckv, p["w_uv"].astype(dt))
        return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(dt))
    hp, head_mask = _padded_heads(cfg)
    hk, hd = cfg.num_kv_heads, cfg.head_dim
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if "bv" in p:
        v = v + p["bv"].astype(dt)
    B, S = v.shape[:2]
    out = jnp.broadcast_to(v[:, :, :, None, :],
                           (B, S, hk, hp // hk, hd)).reshape(B, S, hp, hd)
    if head_mask is not None:
        out = out * jnp.asarray(head_mask, dt)[None, None, :, None]
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def _mtp_block(cfg, p, x, dt):
    """norm1 → self-only attention → residual → norm2 → mlp → residual —
    the decode-mode twin of the train-mode ``tfm.apply_layer`` call on
    ``params["mtp"]["block"]`` (which is always an ("attn","mlp") layer,
    dense FFN even for MoE trunks)."""
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    x = x + _mtp_self_attention(cfg, p["mixer"], h, dt)
    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    from repro.models.layers import apply_mlp  # local: avoid re-export churn
    return x + apply_mlp(p["ffn"], h, gated=cfg.mlp_gated)


def mtp_draft(cfg, params, hidden, tokens, k):
    """Greedy-draft ``k`` future tokens from the trunk's last hidden state.

    EAGLE-style chained depth-1 drafting with the DeepSeek MTP head:
    each step combines the current hidden (``norm_h``) with the
    embedding of the newest token (``norm_e``), projects the concat back
    to ``d_model``, runs the MTP transformer block (window-1 attention —
    see :func:`_mtp_self_attention`), reads a greedy token off the
    shared unembedding, and feeds the block's output hidden + the new
    draft's embedding back in for the next step.  This mirrors the
    train-mode head exactly at chain depth 1: hidden at position ``i``
    plus token ``i+1`` predicts token ``i+2``.

    hidden : (B, 1, d) trunk hidden at the last accepted position
             (``apply_model(...)["hidden"]``, pre-final-norm).
    tokens : (B, 1) int32 — the newest committed/accepted token.
    Returns (draft_tokens (B, k) int32, last_hidden (B, 1, d)).
    """
    if cfg.mtp_depth <= 0:
        raise ValueError("mtp_draft needs cfg.mtp_depth > 0 (no MTP head "
                         "in this architecture)")
    dt = jnp.dtype(cfg.dtype)
    p = params["mtp"]
    h, t = hidden.astype(dt), tokens
    drafts = []
    for _ in range(k):
        e = apply_embed(params["embed"], t, dt)
        hm = jnp.concatenate(
            [rmsnorm(p["norm_h"], h, cfg.norm_eps),
             rmsnorm(p["norm_e"], e, cfg.norm_eps)],
            axis=-1) @ p["proj"].astype(dt)
        hm = _mtp_block(cfg, p["block"], hm, dt)
        t = jnp.argmax(_logits(cfg, params, hm), axis=-1).astype(jnp.int32)
        h = hm
        drafts.append(t[:, 0])
    return jnp.stack(drafts, axis=1), h


# ==========================================================================
# Paper Table-1 networks
# ==========================================================================

def init_paper_net(net, key):
    ks = jax.random.split(key, 16)
    if net.kind == "dnn":
        params = {"layers": []}
        for i, (din, dout) in enumerate(
                zip(net.layer_sizes[:-1], net.layer_sizes[1:])):
            params["layers"].append({
                "w": dense_init(ks[i], din, dout),
                "b": jnp.zeros((dout,), jnp.float32)})
        return params
    # CNN: 5x5 convs + 2x2 pools, then sigmoid FC, then softmax out
    params = {"conv": [], "fc": []}
    cin = net.image_channels
    h, w = net.image_hw
    for i, cout in enumerate(net.conv_channels):
        params["conv"].append({
            "w": truncated_normal(ks[i], (5, 5, cin, cout),
                                  (2.0 / (25 * cin)) ** 0.5),
            "b": jnp.zeros((cout,), jnp.float32)})
        cin = cout
        h, w = h // 2, w // 2        # 2x2 max-pool after each conv
    flat = h * w * cin
    params["fc"].append({"w": dense_init(ks[8], flat, net.fc_size),
                         "b": jnp.zeros((net.fc_size,), jnp.float32)})
    params["fc"].append({"w": dense_init(ks[9], net.fc_size, net.num_classes),
                         "b": jnp.zeros((net.num_classes,), jnp.float32)})
    return params


def apply_paper_net(net, params, x):
    """x: (B, features) for DNN; (B, H, W, C) for CNN.  Returns logits."""
    if net.kind == "dnn":
        h = x
        for i, layer in enumerate(params["layers"]):
            h = h @ layer["w"] + layer["b"]
            if i < len(params["layers"]) - 1:
                h = jax.nn.sigmoid(h)
        return h
    h = x
    for layer in params["conv"]:
        h = jax.lax.conv_general_dilated(
            h, layer["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + layer["b"]
        h = jax.nn.relu(h)
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.sigmoid(h @ params["fc"][0]["w"] + params["fc"][0]["b"])
    return h @ params["fc"][1]["w"] + params["fc"][1]["b"]
