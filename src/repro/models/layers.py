"""Core building blocks: norms, MLPs, embeddings, RoPE.

Pure functional style: ``init_*`` returns a param pytree (fp32 master
weights), ``apply_*`` consumes it.  Compute happens in ``cfg.dtype``
(bf16 by default); params are cast at the point of use so fp32 masters
are preserved for the optimizer (TPU-native mixed precision — a
documented adaptation from the paper's fp32-on-CPU setup).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import ctx as shctx


def truncated_normal(key, shape, std, dtype=jnp.float32):
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


def dense_init(key, d_in, d_out, *, std=None, dtype=jnp.float32):
    std = std if std is not None else 1.0 / np.sqrt(d_in)
    return truncated_normal(key, (d_in, d_out), std, dtype)


def scoped(name):
    """Decorator: trace the function under ``jax.named_scope(name)``, so
    its operations carry ``name`` in their ``op_name`` metadata and a
    profiler trace can attribute device time to it.  Enters a fresh
    scope per call: ``named_scope``'s own decorator form shares one
    context object between calls, which nested tracing would corrupt."""
    def deco(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return run
    return deco


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"]).astype(dt)


def rmsnorm_nop(x, eps=1e-6):
    """Scale-free rmsnorm (qk-norm without learned scale uses this form)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt)


def init_layernorm(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * p["scale"] + p["bias"]).astype(dt)


# --------------------------------------------------------------------------
# MLP (gated SwiGLU or plain 2-mat)
# --------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, gated=True):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff),
         "w_down": dense_init(ks[1], d_ff, d_model)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff)
    return p


def apply_mlp(p, x, gated=True):
    dt = x.dtype
    up = x @ p["w_up"].astype(dt)
    if gated:
        h = jax.nn.silu(x @ p["w_gate"].astype(dt)) * up
    else:
        h = jax.nn.gelu(up)
    return out_proj(h, p["w_down"].astype(dt))


def out_proj(x, w, eq=None):
    """A block's output projection in ``x``'s dtype: ``x @ w``, or
    ``einsum(eq, x, w)``.  Under a model-sharded serve topology its
    contracting axis (the heads, or the FFN width) is split over
    "model": each device's partial product would be rounded to ``x``'s
    dtype before the all-reduce sums them, and in bfloat16 that moves
    the logits by ulps, enough to flip a near-tied expert choice or
    greedy token.  There the partial products and their sum stay in
    float32 and are rounded once, as on one device."""
    if not shctx.serve_model_sharded():
        return x @ w if eq is None else jnp.einsum(eq, x, w)
    f32 = jnp.float32
    y = (jnp.matmul(x, w, preferred_element_type=f32) if eq is None
         else jnp.einsum(eq, x, w, preferred_element_type=f32))
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# Embeddings
# --------------------------------------------------------------------------

def init_embed(key, vocab, d_model):
    return {"table": truncated_normal(key, (vocab, d_model), 0.02)}


def apply_embed(p, tokens, dtype):
    return p["table"].astype(dtype)[tokens]


def apply_unembed(p, x):
    # logits in fp32 for a numerically stable loss
    return x.astype(jnp.float32) @ p["table"].astype(jnp.float32)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def apply_rope(x, positions, theta):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))            # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]                  # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
