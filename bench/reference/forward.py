"""The plain reference: a float32 forward pass from the published
equations, layer by layer.

It imports nothing of the program.  Its weights are the benchmark's
(``bench.model``), drawn again from the seed one layer at a time, so
the whole model never has to sit in float32 on the chip.

Per layer (Qwen3 and DeepSeekMoE alike)::

    h = x + Attn(RMSNorm(x))
    y = h + FFN(RMSNorm(h))

Attn: q = x Wq, k = x Wk, v = x Wv per head; Qwen3 normalises q and k
per head (RMSNorm over the head dimension, with a learned scale);
rotary embedding on the two halves of each head (theta from the
config); causal softmax(q k^T / sqrt(d_head)) v, query head i reading
key/value head i // (heads / kv_heads); out = concat(heads) Wo.

FFN: SwiGLU, (silu(x Wgate) * (x Wup)) Wdown.  DeepSeekMoE layers after
the first: s = softmax(x Wrouter) over the routed experts; the top k
experts by s; routed = sum over them of g_e FFN_e(x), with g_e = s_e,
or s_e renormalised over the k when the config says
``norm_topk_prob``; out = routed + the shared experts' FFN (one SwiGLU
of n_shared x moe_intermediate_size).

Then the final RMSNorm and the head: the embedding table when tied,
else its own.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as bmodel

Q_CHUNK = 512


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x: (..., L, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def attention(cfg, p, x, eps):
    """x: (B, L, d) -> (B, L, d), causal over each row."""
    B, L, _ = x.shape
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, p["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, p["wv"])
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"]["scale"], eps)
        k = rmsnorm(k, p["k_norm"]["scale"], eps)
    pos = jnp.arange(L)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    g = h // hk
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scale = q.shape[-1] ** -0.5
    n = -(-L // Q_CHUNK)
    pad = n * Q_CHUNK - L
    qc = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        B, n, Q_CHUNK, h, -1).transpose(1, 0, 2, 3, 4)

    def chunk(_, args):
        i, qi = args
        s = jnp.einsum("bqhk,bthk->bhqt", qi, k) * scale
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        return None, jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)

    _, out = jax.lax.scan(chunk, None, (jnp.arange(n), qc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, n * Q_CHUNK, h, -1)[:, :L]
    return jnp.einsum("blhk,hkd->bld", out, p["wo"])


def moe(cfg, p, x):
    """Every routed expert over every token, weighted by its gate (zero
    outside the top k): plain, and exact for any routing."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.softmax(x @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(s, k)
    if cfg.get("norm_topk_prob"):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, E, dtype=x.dtype) * top[..., None],
                   axis=-2)                                # (..., E)
    ex = p["experts"]

    def one(acc, args):
        w, g = args
        return acc + g[..., None] * swiglu(x, w), None

    per_expert = {n: ex[n] for n in ("w_gate", "w_up", "w_down")}
    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             (per_expert, jnp.moveaxis(gate, -1, 0)))
    return routed + swiglu(x, p["shared"])


def layer(cfg, p, x):
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, p["mixer"], rmsnorm(x, p["norm1"]["scale"], eps),
                      eps)
    h = rmsnorm(x, p["norm2"]["scale"], eps)
    f = p["ffn"]
    return x + (moe(cfg, f, h) if "router" in f else swiglu(h, f))


def _f32(tree, transform):
    return jax.tree_util.tree_map(
        lambda w: transform(w.astype(jnp.float32)), tree)


def identity(w):
    return w


def fp8_weights(w):
    """The control's step down from bfloat16: each matrix rounded to
    float8 e4m3 with one scale per tensor (vectors kept)."""
    if w.ndim < 2:
        return w
    amax = jnp.max(jnp.abs(w))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def logits_at(cfg: dict, seed: int, rows: List[np.ndarray],
              wanted: List[np.ndarray],
              transform: Callable = identity) -> List[np.ndarray]:
    """Reference logits of each row of tokens at the wanted positions.

    Rows are padded at the end to one length (causal attention keeps
    padding from reaching an earlier position) and run as one batch,
    layer by layer.  ``transform`` maps each float32 weight before use
    (the control passes ``fp8_weights``)."""
    L = max(len(r) for r in rows)
    L = -(-L // 1024) * 1024            # few shapes, so compiles are cached
    toks = np.zeros((len(rows), L), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    n_layers = cfg["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        outer = _f32(bmodel.draw_outer(cfg, seed), transform)
        x = _embed(outer["embed"]["table"], jnp.asarray(toks))
        for i in range(n_layers):
            p = _f32(bmodel.draw_layer(cfg, seed, i), transform)
            x = _layer(cfg_key(cfg), p, x)
        at_r = np.concatenate([np.full(len(w), i) for i, w in
                               enumerate(wanted)])
        at_t = np.concatenate(wanted)
        head = (outer["embed"]["table"] if cfg["tie_word_embeddings"]
                else outer["unembed"]["table"])
        out = _head(cfg_key(cfg), x, jnp.asarray(at_r), jnp.asarray(at_t),
                    outer["final_norm"]["scale"], head)
    out = np.asarray(out)
    splits = np.cumsum([len(w) for w in wanted])[:-1]
    return np.split(out, splits)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def cfg_key(cfg: dict) -> _Frozen:
    keep = ("num_attention_heads", "num_key_value_heads", "rope_theta",
            "rms_norm_eps", "n_routed_experts", "num_experts_per_tok",
            "norm_topk_prob")
    return _Frozen({k: cfg[k] for k in keep if k in cfg})


@jax.jit
def _embed(table, toks):
    return table[toks]


@functools.partial(jax.jit, static_argnums=0)
def _layer(cfg, p, x):
    return layer(cfg, p, x)


@functools.partial(jax.jit, static_argnums=0)
def _head(cfg, x, rows, cols, scale, table):
    h = rmsnorm(x[rows, cols], scale, cfg["rms_norm_eps"])
    return h @ table.T


def greedy_gaps(ref: np.ndarray, served: np.ndarray,
                other: Optional[np.ndarray] = None) -> np.ndarray:
    """How far below the reference's best logit each served token's
    logit lies; with ``other`` (the control's logits), the token that
    ``other`` puts first stands in for the served one."""
    tok = served if other is None else np.argmax(other, axis=-1)
    best = ref.max(axis=-1)
    return best - ref[np.arange(len(tok)), tok]
