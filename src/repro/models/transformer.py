"""Decoder stack assembly: heterogeneous layers, scan-over-superblocks.

``cfg.block_structure()`` splits the depth into an unrolled prefix (e.g.
DeepSeek's leading dense-FFN layers) plus a repeating super-block (e.g.
Jamba's 8-layer mamba/attn/MoE period).  The super-block is applied with
``jax.lax.scan`` over stacked params so the lowered HLO contains ONE
copy of the block body regardless of depth — this keeps 62-layer models
SPMD-partitionable in reasonable compile time and is also what makes
activation rematerialisation per-block natural.

Caches are pytrees mirroring the param structure:
  attn  -> {"k","v"}            (B, S_max, hk, hd)
  mla   -> {"ckv","krope"}      (B, S_max, r)
  mamba -> {"ssm","conv"}       (B, dI, dS) / (B, dc-1, dI)
  rwkv6 -> {"state","shift_tm","shift_cm"}
stacked with a leading (n_repeats,) axis for the scanned blocks.

Under a paged view (serving) an attention/MLA cache is instead a page
pool shared by every slot, ``(n_repeats, N, ...)`` with no slot axis.
Those pools ride the layer scan's CARRY whole: each layer scatters its
new entries into, and gathers its pages from, its own rows of the
stacked leaf at the scan counter, so no layer's pool is sliced out of
the stack and re-stacked, and the donated pool is updated in place.
Per-slot leaves (recurrent SSM state) and slab caches are small and
ride ``xs``/``ys`` as before.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    init_rmsnorm, rmsnorm, init_mlp, apply_mlp, dense_init, scoped)
from repro.sharding.ctx import constrain_bsd


# --------------------------------------------------------------------------
# single layer
# --------------------------------------------------------------------------

def init_layer(cfg, key, spec, *, dense_ff=None, cross=False):
    mixer, ffn = spec
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p = {"norm1": init_rmsnorm(d)}
    if mixer == "attn":
        p["mixer"] = (attn_lib.init_mla(cfg, ks[0])
                      if cfg.attention == "mla"
                      else attn_lib.init_attention(cfg, ks[0]))
    elif mixer == "mamba":
        p["mixer"] = ssm_lib.init_mamba(cfg, ks[0])
    elif mixer == "rwkv6":
        # rwkv block: norm1+time-mix, norm2+channel-mix (its own "ffn")
        p["mixer"] = ssm_lib.init_rwkv6(cfg, ks[0])
        p["norm2"] = init_rmsnorm(d)
        return p
    if cross:
        p["norm_cross"] = init_rmsnorm(d)
        p["cross"] = attn_lib.init_attention(cfg, ks[1], cross=True)
    p["norm2"] = init_rmsnorm(d)
    if ffn == "moe":
        p["ffn"] = moe_lib.init_moe(cfg, ks[2])
    else:
        ff = dense_ff or cfg.d_ff
        p["ffn"] = init_mlp(ks[2], d, ff, gated=cfg.mlp_gated)
    return p


def pooled(spec) -> bool:
    """Whether a paged cache makes this layer's cache a page pool shared
    by every slot (attention and MLA K/V: token-major, no slot axis)
    rather than per-slot state (recurrent SSM)."""
    return spec[0] == "attn"


def init_layer_cache(cfg, spec, batch, max_len, dtype, *, cross=False,
                     cross_len=0, pool=None):
    """pool=(num_pages, page_size): attention/MLA caches become shared
    token-major page pools (no batch axis — serve.kvcache allocates
    pages to slots); recurrent SSM state stays per-slot (O(1) in
    context, nothing to page)."""
    mixer, _ = spec
    pool = pool if pooled(spec) else None
    if mixer == "attn":
        c = (attn_lib.make_mla_cache(cfg, batch, max_len, dtype, pool=pool)
             if cfg.attention == "mla"
             else attn_lib.make_cache(cfg, batch, max_len, dtype, pool=pool))
    elif mixer == "mamba":
        c = ssm_lib.make_mamba_cache(cfg, batch, dtype)
    elif mixer == "rwkv6":
        c = ssm_lib.make_rwkv6_cache(cfg, batch, dtype)
    else:
        raise ValueError(mixer)
    if cross:
        if pool is not None:
            raise ValueError("paged cache does not support cross-attention")
        c = {"self": c,
             "cross": attn_lib.make_cache(cfg, batch, cross_len, dtype)}
    return c


@scoped("layer")
def apply_layer(cfg, spec, p, x, *, positions, mode, cache=None,
                cache_pos=None, enc_out=None, causal=True, paged=None):
    """Returns (x, new_cache, aux_loss)."""
    mixer, ffn = spec
    aux = jnp.zeros((), jnp.float32)
    self_cache = cache["self"] if (cache is not None and "self" in cache) else cache

    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        if cfg.attention == "mla":
            h, new_self = attn_lib.apply_mla(
                cfg, p["mixer"], h, positions=positions, mode=mode,
                cache=self_cache, cache_pos=cache_pos, paged=paged)
        else:
            h, new_self = attn_lib.apply_attention(
                cfg, p["mixer"], h, positions=positions, mode=mode,
                cache=self_cache, cache_pos=cache_pos, causal=causal,
                rope=True, paged=paged)
    elif mixer == "mamba":
        h, new_self = ssm_lib.apply_mamba(cfg, p["mixer"], h, mode=mode,
                                          cache=self_cache)
    elif mixer == "rwkv6":
        h, new_self = ssm_lib.apply_rwkv6_time_mix(
            cfg, p["mixer"], h, mode=mode, cache=self_cache)
        x = x + h
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        h2, new_self = ssm_lib.apply_rwkv6_channel_mix(
            cfg, p["mixer"], h2,
            cache=new_self)
        x = x + h2
        return x, new_self, aux
    x = x + h

    new_cache = new_self
    if "cross" in p:
        hc = rmsnorm(p["norm_cross"], x, cfg.norm_eps)
        cross_cache = cache["cross"] if cache is not None else None
        hc, new_cross = attn_lib.apply_attention(
            cfg, p["cross"], hc, positions=positions,
            mode=("decode" if mode == "decode" else mode),
            cache=cross_cache, cache_pos=cache_pos, kv_src=enc_out,
            causal=False)
        x = x + hc
        if cache is not None:
            new_cache = {"self": new_self, "cross": new_cross}

    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if ffn == "moe":
        with jax.named_scope("moe"):
            h, aux = moe_lib.apply_moe(cfg, p["ffn"], h)
    else:
        with jax.named_scope("mlp"):
            h = apply_mlp(p["ffn"], h, gated=cfg.mlp_gated)
    x = x + h
    return x, new_cache, aux


# --------------------------------------------------------------------------
# stack (prefix + scanned super-blocks)
# --------------------------------------------------------------------------

def init_stack(cfg, key, *, cross=False):
    prefix, pattern, n_rep = cfg.block_structure()
    kp, kb = jax.random.split(key)
    params = {}
    dense_ff = cfg.moe.dense_d_ff if cfg.moe is not None else None
    params["prefix"] = {
        f"layer{i}": init_layer(cfg, k, spec, dense_ff=dense_ff, cross=cross)
        for i, (spec, k) in enumerate(
            zip(prefix, jax.random.split(kp, max(1, len(prefix)))))
    } if prefix else {}

    def init_block(k):
        ks = jax.random.split(k, len(pattern))
        return {f"layer{i}": init_layer(cfg, ks[i], spec, cross=cross)
                for i, spec in enumerate(pattern)}

    params["blocks"] = jax.vmap(init_block)(
        jax.random.split(kb, n_rep))
    return params


def init_stack_cache(cfg, batch, max_len, dtype, *, cross=False,
                     cross_len=0, pool=None):
    prefix, pattern, n_rep = cfg.block_structure()
    mk = functools.partial(init_layer_cache, cfg, batch=batch,
                           max_len=max_len, dtype=dtype, cross=cross,
                           cross_len=cross_len, pool=pool)
    cache = {"prefix": {f"layer{i}": mk(spec)
                        for i, spec in enumerate(prefix)} if prefix else {}}

    def one_block():
        return {f"layer{i}": mk(spec) for i, spec in enumerate(pattern)}

    cache["blocks"] = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_rep,) + x.shape)
        if n_rep else x, one_block())
    return cache


@scoped("layers")
def apply_stack(cfg, params, x, *, positions, mode, cache=None,
                cache_pos=None, enc_out=None, causal=True, remat=False,
                paged=None):
    """Returns (x, new_cache, aux)."""
    prefix, pattern, n_rep = cfg.block_structure()
    aux = jnp.zeros((), jnp.float32)
    new_cache = {"prefix": {}, "blocks": None}
    has_cache = cache is not None

    for i, spec in enumerate(prefix):
        c = cache["prefix"][f"layer{i}"] if has_cache else None
        x, nc, a = apply_layer(cfg, spec, params["prefix"][f"layer{i}"], x,
                               positions=positions, mode=mode, cache=c,
                               cache_pos=cache_pos, enc_out=enc_out,
                               causal=causal, paged=paged)
        aux = aux + a
        if has_cache:
            new_cache["prefix"][f"layer{i}"] = nc

    def one_layer(spec):
        def f(p, h, c, layer):
            h = constrain_bsd(h)   # pin batch->data on the residual stream
            view = paged if layer is None else paged._replace(layer=layer)
            return apply_layer(cfg, spec, p, h, positions=positions,
                               mode=mode, cache=c, cache_pos=cache_pos,
                               enc_out=enc_out, causal=causal, paged=view)
        # per-LAYER remat: bwd peak = one layer's residuals (the mamba /
        # wkv chunk-scan trajectories are the big ones), not a block's
        return jax.checkpoint(f) if remat else f

    layer_fns = [one_layer(spec) for spec in pattern]

    # a paged view's pools go into the carry whole
    names = [f"layer{j}" for j in range(len(pattern))]
    blk_cache = cache["blocks"] if has_cache else {}
    pools = {n: blk_cache[n] for n, spec in zip(names, pattern)
             if has_cache and paged is not None and pooled(spec)}
    per_slot = ({n: blk_cache[n] for n in names if n not in pools}
                if has_cache else None)
    counter = jnp.arange(n_rep, dtype=jnp.int32) if pools else None

    def block_body(carry, xs):
        h, aux_acc, pools = carry
        p_blk, c_blk, layer = xs
        pools, nc_blk = dict(pools), {}
        for j, n in enumerate(names):
            if n in pools:
                h, pools[n], a = layer_fns[j](p_blk[n], h, pools[n], layer)
            else:
                c = c_blk[n] if has_cache else None
                h, nc_blk[n], a = layer_fns[j](p_blk[n], h, c, None)
            aux_acc = aux_acc + a
        return (h, aux_acc, pools), (nc_blk if has_cache else None)

    (x, aux, pools), blk_caches = jax.lax.scan(
        block_body, (x, aux, pools), (params["blocks"], per_slot, counter))
    if has_cache:
        new_cache["blocks"] = {**blk_caches, **pools}
    return x, (new_cache if has_cache else None), aux
