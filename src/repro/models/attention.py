"""Attention: MHA/GQA (+qk-norm, qkv-bias, sliding window), MLA, cross-attn.

Memory discipline: full (S, S) score materialisation at 32k+ sequence
lengths does not fit HBM, so prefill/train attention is computed in
query chunks via ``lax.scan`` (flash-attention memory behaviour at the
XLA level; the Pallas kernel in ``repro.kernels.flash_attention`` is the
TPU-optimised version of the same loop).  Decode attends a single query
against the KV cache.

KV caches are dicts of arrays with a leading-batch layout
``(B, S_max, kv_heads, head_dim)`` (MLA: latent ``(B, S_max, r)``).
``cache_pos`` is the number of tokens already in the cache.

Paged serving cache: attention K/V can instead live in a shared *page
pool* with a token-major layout ``(num_pages * page_size, kv_heads,
head_dim)`` (MLA: ``(N, r)``) and no batch axis at all.  A per-slot
page table (``PagedView``) maps each slot's logical token positions to
physical pool slots, so decode reads/writes go through gather/scatter
and every slot only occupies the pages it was allocated —
``repro.serve.kvcache`` owns allocation; this module owns the read
path.  ``cache_pos`` is then a per-slot ``(B,)`` vector, which is what
continuous batching needs (slots at different depths in one step).
The layers of a scanned block keep their pools stacked ``(n_rep, N,
...)``; the layer scan hands each layer a view that names its index,
and the paged paths write and read that layer's rows in place.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (dense_init, rmsnorm_nop, apply_rope,
                                 init_rmsnorm, out_proj, rmsnorm, scoped)
from repro.sharding.ctx import (constrain_paged_kv, constrain_paged_latent,
                                replicate_update, serve_model_sharded)

NEG_INF = -1e30


class PagedView(NamedTuple):
    """How a (decode-mode) model call should read a paged KV cache.

    page_table — (B, table_width) int32: physical page id of each
                 slot's logical block (0 = the reserved trash page,
                 used both for never-allocated blocks and as the write
                 sink of idle slots, whose table rows are all zero).
    page_size  — tokens per page; static under jit (close over it).
    layer      — None: a cache leaf is one layer's pool (N, ...).  Set
                 by the layer scan to the layer's index in its block:
                 the leaf is then the block's stacked pool
                 (n_rep, N, ...), written and read in place.
    """
    page_table: Any
    page_size: int
    layer: Any = None


# --------------------------------------------------------------------------
# chunked softmax attention core
# --------------------------------------------------------------------------

def _grouped_scores(qc, k):
    # qc: (B, hk, g, Cq, hd)  k: (B, T, hk, hd) -> (B, hk, g, Cq, T)
    return jnp.einsum("bkgqd,btkd->bkgqt", qc, k,
                      preferred_element_type=jnp.float32)


def _grouped_out(probs, v):
    # probs: (B, hk, g, Cq, T)  v: (B, T, hk, hd) -> (B, hk, g, Cq, hd)
    return jnp.einsum("bkgqt,btkd->bkgqd", probs.astype(v.dtype), v)


def chunked_attention(q, k, v, *, q_positions, kv_positions, causal=True,
                      window=0, kv_valid_len=None, chunk=1024):
    """q: (B,S,h,hd); k,v: (B,T,hk,hd).  Returns (B,S,h,hd).

    q_positions: (S,) global positions of queries.
    kv_positions: (T,) global positions of keys.
    kv_valid_len: scalar — keys at kv_positions >= this are masked
        (used at decode where the cache tail is unwritten).
    """
    B, S, h, hd = q.shape
    T, hk = k.shape[1], k.shape[2]
    g = h // hk
    scale = 1.0 / np.sqrt(hd)

    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad), constant_values=-1)
    nc = q.shape[1] // chunk

    qg = q.reshape(B, nc, chunk, hk, g, hd).transpose(1, 0, 3, 4, 2, 5)
    qp = q_positions.reshape(nc, chunk)

    def step(_, inp):
        qc, qpos = inp                                   # (B,hk,g,Cq,hd), (Cq,)
        s = _grouped_scores(qc, k) * scale               # (B,hk,g,Cq,T) fp32
        m = jnp.ones((chunk, T), bool)
        if causal:
            m &= kv_positions[None, :] <= qpos[:, None]
        if window:
            m &= kv_positions[None, :] > qpos[:, None] - window
        if kv_valid_len is not None:
            m &= kv_positions[None, :] < kv_valid_len
        m &= qpos[:, None] >= 0                          # query padding
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return None, _grouped_out(p, v)                  # (B,hk,g,Cq,hd)

    # checkpoint each q-chunk: bwd recomputes the (Cq, T) score/prob
    # tiles instead of saving them for every chunk — flash-attention
    # memory behaviour under autodiff
    _, out = jax.lax.scan(jax.checkpoint(step), None, (qg, qp))
    hd_v = v.shape[-1]                                   # may differ (MLA)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, nc * chunk, h, hd_v)
    return out[:, :S]


def masked_attention(q, k, v, *, q_positions, kv_positions, window=0):
    """Per-slot-position attention core for the paged serve path.

    q: (B, S, h, hd); k, v: (B, T, hk, hd); q_positions: (B, S) global
    positions per slot; kv_positions: (T,) logical cache positions.
    Key t is visible to query (b, s) iff ``kv_positions[t] <=
    q_positions[b, s]`` (within the sliding window when set) — the
    causal mask alone covers cache validity, since every position <=
    the query's has been written by this slot.  Single q-chunk (the
    same einsums, shapes and masking value as one ``chunked_attention``
    step, so greedy decode is bitwise-identical to the slab path): S is
    a decode token or a prefill chunk here, never a 32k sequence.
    """
    B, S, h, hd = q.shape
    hk = k.shape[2]
    g = h // hk
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, S, hk, g, hd).transpose(0, 2, 3, 1, 4)
    s = _grouped_scores(qg, k) * scale               # (B,hk,g,S,T) fp32
    m = kv_positions[None, None, :] <= q_positions[:, :, None]   # (B,S,T)
    if window:
        m &= kv_positions[None, None, :] > q_positions[:, :, None] - window
    m &= q_positions[:, :, None] >= 0                # query padding
    s = jnp.where(m[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _grouped_out(p, v)                         # (B,hk,g,S,hd_v)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, h, v.shape[-1])


# --------------------------------------------------------------------------
# paged-pool addressing (repro.serve.kvcache allocates; this reads/writes)
# --------------------------------------------------------------------------

def paged_write_indices(paged: PagedView, positions):
    """(B, S) logical positions -> (B, S) physical pool-token indices.
    Out-of-range / negative positions map to the trash page (page 0),
    so padded prefill lanes and idle slots scatter harmlessly."""
    table = paged.page_table
    bs = paged.page_size
    width = table.shape[1]
    pos = jnp.clip(positions, 0, width * bs - 1)
    phys = jnp.take_along_axis(table, pos // bs, axis=1) * bs + pos % bs
    valid = (positions >= 0) & (positions < width * bs)
    return jnp.where(valid, phys, 0)


def pool_rows(pool_leaf, paged: PagedView):
    """A pool leaf as token rows ``(tokens, ...)`` and the first row of
    ``paged``'s layer.  One layer's pool ``(N, ...)`` starts at row 0.
    A scanned block's stacked pool ``(n_rep, N, ...)`` numbers its rows
    layer after layer, so layer ``l`` starts at row ``l * N`` and is
    written and read in place: ``pool_leaf[l]`` is never formed."""
    if paged.layer is None:
        return pool_leaf, 0
    return (pool_leaf.reshape((-1,) + pool_leaf.shape[2:]),
            paged.layer * pool_leaf.shape[1])


def pool_pages(pool_leaf, paged: PagedView):
    """The pool leaf as pages ``(pages, page_size, ...)`` and the page
    table of ``paged``'s layer that addresses them, for a read.  Under
    a model-sharded serve topology the layer's rows are sliced out
    first.  Each device there holds a few heads (or a slice of
    head_dim) of every token, a layout the gather does not read in
    place: reading the stack would relayout every layer's pages in each
    layer, and the kernels' replicated operands would all-gather it."""
    if paged.layer is not None and serve_model_sharded():
        pool_leaf = jax.lax.dynamic_index_in_dim(pool_leaf, paged.layer,
                                                 keepdims=False)
        paged = paged._replace(layer=None)
    rows, first = pool_rows(pool_leaf, paged)
    bs = paged.page_size
    return (rows.reshape((-1, bs) + rows.shape[1:]),
            paged.page_table + first // bs)


def paged_read(pool_leaf, paged: PagedView):
    """Gather a slot-major view (B, L, ...) out of a token-major pool
    (N, ...), L = table_width * page_size.  Unallocated blocks gather
    the trash page and are masked by the causal/validity mask.  The
    gather is PAGE-granular — whole contiguous pages, table_width rows
    per slot — not per-token: on CPU/XLA a token-granular gather
    scalarises and eats the fused-loop dispatch win.  A stacked pool's
    layer is addressed by ``pool_pages``."""
    pages, table = pool_pages(pool_leaf, paged)
    B, width = table.shape
    full = pages[table]                       # (B, width, bs, ...)
    return (full.reshape((B, width * pages.shape[1]) + pages.shape[2:]),
            jnp.arange(width * pages.shape[1]))


def _paged_append(pool_leaf, paged: PagedView, positions, new):
    """Scatter S new per-slot entries (B, S, ...) into the pool — a
    stacked pool's layer rows in place (``pool_rows``)."""
    rows, first = pool_rows(pool_leaf, paged)
    idx = first + paged_write_indices(paged, positions).reshape(-1)
    flat = new.reshape((-1,) + new.shape[2:]).astype(pool_leaf.dtype)
    return rows.at[idx].set(flat).reshape(pool_leaf.shape)


def _pos2d(positions):
    """Normalise positions to (B, S) for rope / per-slot masking."""
    return positions if positions.ndim == 2 else positions[None]


# --------------------------------------------------------------------------
# GQA attention layer
# --------------------------------------------------------------------------

def _padded_heads(cfg):
    """(h_padded, real_head_mask or None).  Padding layout: each kv head's
    group is padded at the END (q head j of kv head i sits at i*g_new+j),
    so GQA grouping stays aligned and the padded slots are exact zeros."""
    h, hk = cfg.num_heads, cfg.num_kv_heads
    if not cfg.pad_heads_to or cfg.pad_heads_to == h:
        return h, None
    hp = cfg.pad_heads_to
    assert hp % hk == 0 and hp > h
    g_old, g_new = h // hk, hp // hk
    mask = np.zeros((hp,), np.float32)
    for i in range(hk):
        mask[i * g_new:i * g_new + g_old] = 1.0
    return hp, mask


def init_attention(cfg, key, *, cross=False):
    d, hk, hd = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    h, mask = _padded_heads(cfg)
    ks = jax.random.split(key, 6)
    wq = dense_init(ks[0], d, h * hd).reshape(d, h, hd)
    wo = dense_init(ks[3], h * hd, d).reshape(h, hd, d)
    if mask is not None:
        wq = wq * mask[None, :, None]
        wo = wo * mask[:, None, None]
    p = {
        "wq": wq,
        "wk": dense_init(ks[1], d, hk * hd).reshape(d, hk, hd),
        "wv": dense_init(ks[2], d, hk * hd).reshape(d, hk, hd),
        "wo": wo,
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((hk, hd), jnp.float32)
        p["bv"] = jnp.zeros((hk, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def make_cache(cfg, batch, max_len, dtype, *, pool=None):
    hk, hd = cfg.num_kv_heads, cfg.head_dim
    if pool is not None:
        num_pages, page_size = pool
        n = num_pages * page_size
        return {"k": jnp.zeros((n, hk, hd), dtype),
                "v": jnp.zeros((n, hk, hd), dtype)}
    return {"k": jnp.zeros((batch, max_len, hk, hd), dtype),
            "v": jnp.zeros((batch, max_len, hk, hd), dtype)}


def apply_attention(cfg, p, x, *, positions, mode="train", cache=None,
                    cache_pos=None, kv_src=None, causal=True, rope=None,
                    paged=None):
    """Self- or cross-attention.

    mode: 'train' (no cache), 'prefill' (fill + return cache),
          'decode' (read/update cache, x is (B,1,d)).
    kv_src: encoder output for cross-attention ('train'/'prefill' only;
          decode reads the cross cache without touching kv_src).
    rope: apply rotary embeddings; defaults to `causal` (self-attention
          yes, cross-attention no; bidirectional encoders pass rope=True).
    paged: PagedView — decode-mode only: `cache` is a token-major page
          pool, `positions` is per-slot (B, S), reads/writes go through
          the page table (a scanned layer's: its rows of the
          block's stacked pool, `PagedView.layer`).
    """
    dt = x.dtype
    B = x.shape[0]
    window = cfg.swa_window
    rope = causal if rope is None else rope

    with jax.named_scope("attn.qkv"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        if "bq" in p:
            q = q + p["bq"].astype(dt)
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q, cfg.norm_eps)

    if paged is not None:
        if mode != "decode" or not causal:
            raise ValueError("paged KV cache is decode-mode "
                             "self-attention only")
        pos2 = _pos2d(positions)
        src = kv_src if kv_src is not None else x
        with jax.named_scope("attn.qkv"):
            k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(dt))
            if "bk" in p:
                k = k + p["bk"].astype(dt)
                v = v + p["bv"].astype(dt)
            if cfg.qk_norm:
                k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
            if rope:
                k = apply_rope(k, pos2, cfg.rope_theta)
                q = apply_rope(q, pos2, cfg.rope_theta)
        with jax.named_scope("attn.kv_write"):
            # pin the UPDATE replicated before the scatter: rope's
            # split/concat along a model-sharded head_dim otherwise
            # leaves GSPMD free to partition the scatter update in a way
            # that miscombines the halves inside the layer scan
            # (observed on the CPU SPMD partitioner); host mesh: no-op
            k = replicate_update(k)
            v = replicate_update(v)
            k_pool = _paged_append(cache["k"], paged, pos2, k)
            v_pool = _paged_append(cache["v"], paged, pos2, v)
        if cfg.decode_kernel == "pallas":
            # fused path: the page-table gather never materialises —
            # the kernel's BlockSpec index map streams pages from the
            # pool into the online-softmax loop
            from repro.kernels.paged_decode import paged_flash_decode
            with jax.named_scope("attn.kv_read"):
                out = paged_flash_decode(
                    q, k_pool, v_pool, paged.page_table, pos2,
                    page_size=paged.page_size, window=window,
                    layer=paged.layer)
        else:
            # spec-aware read: keep the pool's "model" sharding (heads
            # or head_dim) pinned through the page-table gather under a
            # serve topology — a no-op on the host mesh
            with jax.named_scope("attn.kv_read"):
                k_full, kv_positions = paged_read(k_pool, paged)
                v_full, _ = paged_read(v_pool, paged)
                k_full = constrain_paged_kv(k_full).astype(dt)
                v_full = constrain_paged_kv(v_full).astype(dt)
            with jax.named_scope("attn.core"):
                out = masked_attention(q, k_full, v_full,
                                       q_positions=pos2,
                                       kv_positions=kv_positions,
                                       window=window)
        return _attn_out(cfg, p, out, dt), {"k": k_pool, "v": v_pool}

    if mode == "decode" and kv_src is None and not causal:
        # cross-attention decode: cache holds the full encoder K/V
        k, v = cache["k"], cache["v"]
        new_cache = cache
        kv_positions = jnp.arange(k.shape[1])
        kv_valid = None
    else:
        src = kv_src if kv_src is not None else x
        with jax.named_scope("attn.qkv"):
            k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(dt))
            if "bk" in p:
                k = k + p["bk"].astype(dt)
                v = v + p["bv"].astype(dt)
            if cfg.qk_norm:
                k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
            if rope:
                k = apply_rope(k, positions[None], cfg.rope_theta)

        if mode == "train":
            new_cache = None
            kv_positions = positions
            kv_valid = None
        elif mode == "prefill":
            with jax.named_scope("attn.kv_write"):
                new_cache = {"k": k, "v": v} if cache is None else {
                    "k": jax.lax.dynamic_update_slice_in_dim(
                        cache["k"], k.astype(cache["k"].dtype), 0, axis=1),
                    "v": jax.lax.dynamic_update_slice_in_dim(
                        cache["v"], v.astype(cache["v"].dtype), 0, axis=1)}
            kv_positions = positions
            kv_valid = None
        else:  # decode self-attention: append to cache, attend over prefix
            with jax.named_scope("attn.kv_write"):
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), cache_pos,
                    axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), cache_pos,
                    axis=1)
            new_cache = {"k": k_cache, "v": v_cache}
            with jax.named_scope("attn.kv_read"):
                k, v = k_cache.astype(dt), v_cache.astype(dt)
            kv_positions = jnp.arange(k.shape[1])
            kv_valid = cache_pos + x.shape[1]

    if rope:
        with jax.named_scope("attn.qkv"):
            q = apply_rope(q, positions[None], cfg.rope_theta)

    with jax.named_scope("attn.core"):
        out = chunked_attention(
            q, k, v, q_positions=positions, kv_positions=kv_positions,
            causal=causal, window=window if causal else 0,
            kv_valid_len=kv_valid)
    return _attn_out(cfg, p, out, dt), new_cache


@scoped("attn.out")
def _attn_out(cfg, p, out, dt):
    """Zero the padded heads BEFORE wo, so their (garbage) attention
    outputs contribute neither to the output nor to wo's gradient; then
    the output projection."""
    _, head_mask = _padded_heads(cfg)
    if head_mask is not None:
        out = out * jnp.asarray(head_mask, dt)[None, None, :, None]
    return out_proj(out, p["wo"].astype(dt), "bshk,hkd->bsd")


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# --------------------------------------------------------------------------

def init_mla(cfg, key):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    return {
        "w_dq": dense_init(ks[0], d, m.q_lora_rank),
        "q_norm": init_rmsnorm(m.q_lora_rank),
        "w_uq": dense_init(ks[1], m.q_lora_rank, H * qk_hd).reshape(
            m.q_lora_rank, H, qk_hd),
        "w_dkv": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim),
        "kv_norm": init_rmsnorm(m.kv_lora_rank),
        "w_uk": dense_init(ks[3], m.kv_lora_rank, H * m.qk_nope_head_dim
                           ).reshape(m.kv_lora_rank, H, m.qk_nope_head_dim),
        "w_uv": dense_init(ks[4], m.kv_lora_rank, H * m.v_head_dim
                           ).reshape(m.kv_lora_rank, H, m.v_head_dim),
        "wo": dense_init(ks[5], H * m.v_head_dim, d).reshape(
            H, m.v_head_dim, d),
    }


def make_mla_cache(cfg, batch, max_len, dtype, *, pool=None):
    m = cfg.mla
    if pool is not None:
        num_pages, page_size = pool
        n = num_pages * page_size
        return {"ckv": jnp.zeros((n, m.kv_lora_rank), dtype),
                "krope": jnp.zeros((n, m.qk_rope_head_dim), dtype)}
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}


@scoped("attn.qkv")
def _mla_qkv(cfg, p, x, positions):
    m = cfg.mla
    dt = x.dtype
    pos2 = _pos2d(positions)
    ql = rmsnorm(p["q_norm"], x @ p["w_dq"].astype(dt), cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", ql, p["w_uq"].astype(dt))
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], pos2, cfg.rope_theta)
    dkv = x @ p["w_dkv"].astype(dt)
    ckv = rmsnorm(p["kv_norm"], dkv[..., :m.kv_lora_rank], cfg.norm_eps)
    krope = apply_rope(dkv[..., m.kv_lora_rank:][:, :, None, :],
                       pos2, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, ckv, krope


def apply_mla(cfg, p, x, *, positions, mode="train", cache=None,
              cache_pos=None, paged=None):
    m = cfg.mla
    dt = x.dtype
    B, S = x.shape[:2]
    q_nope, q_rope, ckv, krope = _mla_qkv(cfg, p, x, positions)

    if paged is not None:
        if mode != "decode":
            raise ValueError("paged MLA cache is decode-mode only")
        # absorbed decode against the paged latent pool; per-query
        # causal masking (the slab path masks per chunk-end instead)
        pos2 = _pos2d(positions)
        with jax.named_scope("attn.kv_write"):
            # same update-pinning as the GQA path: rope splits the
            # rope-dim and rmsnorm reduces over the latent — both along
            # axes the pool shards over "model"
            ckv = replicate_update(ckv)
            krope = replicate_update(krope)
            ckv_pool = _paged_append(cache["ckv"], paged, pos2, ckv)
            krope_pool = _paged_append(cache["krope"], paged, pos2, krope)
        with jax.named_scope("attn.qkv"):
            q_lat = jnp.einsum("bshn,rhn->bshr", q_nope,
                               p["w_uk"].astype(dt))
        scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
        if cfg.decode_kernel == "pallas":
            from repro.kernels.paged_decode import paged_flash_decode_mla
            with jax.named_scope("attn.kv_read"):
                out_lat = paged_flash_decode_mla(
                    q_lat, q_rope, ckv_pool, krope_pool, paged.page_table,
                    pos2, page_size=paged.page_size, scale=scale,
                    window=cfg.swa_window, layer=paged.layer)
        else:
            with jax.named_scope("attn.kv_read"):
                ckv_c, kv_positions = paged_read(ckv_pool, paged)
                krope_c, _ = paged_read(krope_pool, paged)
                ckv_c = constrain_paged_latent(ckv_c).astype(dt)
                krope_c = constrain_paged_latent(krope_c).astype(dt)
            with jax.named_scope("attn.core"):
                scores = (jnp.einsum("bshr,btr->bhst", q_lat, ckv_c,
                                     preferred_element_type=jnp.float32)
                          + jnp.einsum("bshk,btk->bhst", q_rope, krope_c,
                                       preferred_element_type=jnp.float32))
                scores = scores * scale
                mask = kv_positions[None, None, :] <= pos2[:, :, None]
                if cfg.swa_window:
                    mask &= kv_positions[None, None, :] \
                        > pos2[:, :, None] - cfg.swa_window
                scores = jnp.where(mask[:, None], scores, NEG_INF)
                probs = jax.nn.softmax(scores, axis=-1)
                out_lat = jnp.einsum("bhst,btr->bshr", probs.astype(dt),
                                     ckv_c)
        return (_mla_out(p, out_lat, dt),
                {"ckv": ckv_pool, "krope": krope_pool})

    if mode in ("train", "prefill"):
        # expand latent to per-head K/V; chunked attention as usual
        with jax.named_scope("attn.qkv"):
            k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"].astype(dt))
            v = jnp.einsum("bsr,rhv->bshv", ckv, p["w_uv"].astype(dt))
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    krope[:, :, None, :],
                    k_nope.shape[:3] + (m.qk_rope_head_dim,))],
                axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
        with jax.named_scope("attn.core"):
            out = chunked_attention(q, k, v, q_positions=positions,
                                    kv_positions=positions, causal=True,
                                    window=cfg.swa_window)
        new_cache = None
        if mode == "prefill":
            with jax.named_scope("attn.kv_write"):
                new_cache = {
                    "ckv": jax.lax.dynamic_update_slice_in_dim(
                        cache["ckv"], ckv.astype(cache["ckv"].dtype), 0,
                        axis=1),
                    "krope": jax.lax.dynamic_update_slice_in_dim(
                        cache["krope"], krope.astype(cache["krope"].dtype),
                        0, axis=1)}
        with jax.named_scope("attn.out"):
            out = out_proj(out, p["wo"].astype(dt), "bshv,hvd->bsd")
        return out, new_cache

    # absorbed decode: score/attend in the 512-dim latent space
    with jax.named_scope("attn.kv_write"):
        ckv_c = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), cache_pos, axis=1)
        krope_c = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], krope.astype(cache["krope"].dtype), cache_pos,
            axis=1)
    new_cache = {"ckv": ckv_c, "krope": krope_c}
    T = ckv_c.shape[1]
    with jax.named_scope("attn.qkv"):
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, p["w_uk"].astype(dt))
    with jax.named_scope("attn.kv_read"):
        ckv_c, krope_c = ckv_c.astype(dt), krope_c.astype(dt)
    with jax.named_scope("attn.core"):
        scores = (jnp.einsum("bshr,btr->bhst", q_lat, ckv_c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshk,btk->bhst", q_rope, krope_c,
                               preferred_element_type=jnp.float32))
        scores = scores / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
        kv_positions = jnp.arange(T)
        # per-query causal: a multi-token decode chunk (chunked prefill)
        # must not let token s see tokens s+1.. of its own chunk
        qpos = _pos2d(positions)[0]                      # (S,)
        mask = kv_positions[None, :] <= qpos[:, None]
        if cfg.swa_window:
            mask = mask & (kv_positions[None, :] > qpos[:, None]
                           - cfg.swa_window)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out_lat = jnp.einsum("bhst,btr->bshr", probs.astype(dt), ckv_c)
    return _mla_out(p, out_lat, dt), new_cache


@scoped("attn.out")
def _mla_out(p, out_lat, dt):
    """Latent attention output -> per-head values -> model width."""
    out = jnp.einsum("bshr,rhv->bshv", out_lat, p["w_uv"].astype(dt))
    return out_proj(out, p["wo"].astype(dt), "bshv,hvd->bsd")


def mla_scale_note(cfg):
    """Prefill scaling uses sqrt(nope+rope) inside chunked_attention via
    head_dim of the concatenated q — consistent with decode."""
    return cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
