"""The plain reference for a training cell: the first steps of AdamW on
a float32 model, from the published equations.

It imports nothing of the program.  The weights are drawn again from
the seed (``bench.model.stacked_weights``), the token rows are the
benchmark's own (``bench.train.tokens``), and the step is written out:
the next-token cross-entropy, averaged over every position that has a
next token in the batch, its gradient, and AdamW (Loshchilov & Hutter)::

    m = b1 m + (1 - b1) g          v = b2 v + (1 - b2) g^2
    p = p - lr * (m / (1 - b1^t) / (sqrt(v / (1 - b2^t)) + eps) + wd * p)

The model is ``forward.layer`` over the stacked layers (each one
rematerialised in the backward pass), the final RMSNorm and the tied
head; matrix products at full float32 precision.  The state is spread
over the cell's chips by JAX's own partitioner: each weight on one of
its model axes, the rows of the batch over the chips.

``transform`` maps each weight before the forward uses it, with the
gradient passed straight through (the control rounds to float8);
``rows`` keeps part of each batch (a fault that leaves rows out);
``scale`` multiplies the gradient (a fault that drops the exchange
between chips keeps one chip's rows and its 1/n share).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import model as bmodel
from bench.reference import forward

CE_CHUNK = 1024         # positions per block of the head and the loss


def identity(w):
    return w


def straight_through(transform: Callable) -> Callable:
    """``transform`` in the forward pass, the identity in the backward."""
    if transform is identity:
        return identity
    return lambda w: w + jax.lax.stop_gradient(transform(w) - w)


def loss(cfg: forward._Frozen, params, tokens, transform=identity,
         rows_sharding=None):
    """Mean next-token cross-entropy of ``tokens`` (B, S); the
    activations keep their rows on ``rows_sharding`` where given."""
    tf = straight_through(transform)

    def keep(x):
        if rows_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, rows_sharding)

    outer = params["outer"]
    table = tf(outer["embed"]["table"])
    x = keep(table[tokens])

    @jax.checkpoint
    def body(x, p):
        return keep(forward.layer(cfg, jax.tree_util.tree_map(tf, p),
                                  x)), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    h = forward.rmsnorm(x, outer["final_norm"]["scale"], cfg["rms_norm_eps"])
    B, S, d = h.shape
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    valid = (jnp.arange(S) < S - 1).astype(jnp.float32)
    C = min(CE_CHUNK, S)
    n = S // C

    @jax.checkpoint
    def block(tot, args):
        hc, yc, vc = args                    # (B, C, d), (B, C), (C,)
        logits = hc @ table.T
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, yc[..., None], -1)[..., 0]
        return tot + jnp.sum((lse - picked) * vc), None

    def split(a):
        return jnp.moveaxis(a.reshape(a.shape[0], n, C, *a.shape[2:]),
                            1, 0)
    tot, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32),
                          (split(h), split(nxt),
                           valid.reshape(n, C)))
    return tot / (B * (S - 1))


def adamw(hp: dict, p, m, v, g, t):
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    p = p - hp["lr"] * (mh / (jnp.sqrt(vh) + eps) + hp["weight_decay"] * p)
    return p, m, v


def _spec(name: str, ndim: int, n: int, shape) -> P:
    """Shard a weight on a model axis that the chips divide."""
    axes = [None] * ndim
    for ax in range(ndim - 1, -1, -1):
        if name.startswith("layers") and ax == 0:
            continue
        if shape[ax] % n == 0 and shape[ax] >= n * 64:
            axes[ax] = "x"
            return P(*axes)
    return P()


def shardings(mesh: Mesh, shapes):
    n = mesh.devices.size
    return jax.tree_util.tree_map_with_path(
        lambda path, s: NamedSharding(mesh, _spec(
            bmodel._leaf_name(path), len(s.shape), n, s.shape)), shapes)


@functools.lru_cache(maxsize=None)
def _step_fn(cfg, hp_items, transform, scale, rows_sharding):
    hp = dict(hp_items)

    def step(params, m, v, t, tokens):
        lval, g = jax.value_and_grad(loss, argnums=1)(
            cfg, params, tokens, transform, rows_sharding)
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        new = jax.tree_util.tree_map(
            lambda p, m_, v_, g_: adamw(hp, p, m_, v_, g_, t),
            params, m, v, g)
        istuple = lambda x: isinstance(x, tuple)  # noqa: E731
        p2 = jax.tree_util.tree_map(lambda x: x[0], new, is_leaf=istuple)
        m2 = jax.tree_util.tree_map(lambda x: x[1], new, is_leaf=istuple)
        v2 = jax.tree_util.tree_map(lambda x: x[2], new, is_leaf=istuple)
        return p2, m2, v2, lval, leaf_norms(g)
    return jax.jit(step, donate_argnums=(1, 2))


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """The norm of every leaf, each layer of a stacked leaf apart."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = bmodel._leaf_name(path)
        if name.startswith("layers"):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(
                range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out


def run(cfg: dict, seed: int, batches: List, hp: dict, devices,
        transform: Callable = identity, rows: Optional[slice] = None,
        scale: float = 1.0) -> dict:
    """The reference's readings over the first ``len(batches)`` steps:
    each step's loss, the norm of every leaf of the first gradient, and
    of every leaf's change over the steps.  ``batches`` are the token
    rows (host arrays) of those steps."""
    mesh = Mesh(np.array(devices), ("x",))
    root = bmodel.root_key(seed)
    shapes = jax.eval_shape(lambda r: bmodel.stacked_weights(cfg, r), root)
    sh = shardings(mesh, shapes)
    rows_sh = NamedSharding(mesh, P("x"))
    ck = forward.cfg_key(cfg)
    hp_items = tuple(sorted(hp.items()))
    with jax.default_matmul_precision("highest"):
        p0 = bmodel.stacked_weights(cfg, root, sh)
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                        out_shardings=sh)
        params = jax.tree_util.tree_map(lambda x: x + 0.0, p0)
        m, v = zeros(p0), zeros(p0)
        losses, g1 = [], None
        for i, b in enumerate(batches):
            b = np.asarray(b)[rows or slice(None)]
            split = len(b) % len(devices) == 0
            toks = jax.device_put(b, rows_sh if split
                                  else NamedSharding(mesh, P()))
            fn = _step_fn(ck, hp_items, transform, float(scale),
                          rows_sh if split else None)
            params, m, v, lval, gn = fn(params, m, v,
                                        jnp.float32(i + 1), toks)
            losses.append(float(lval))
            if i == 0:
                g1 = jax.device_get(gn)
        change = jax.device_get(jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)))(params, p0))
    return {"losses": losses, "grad_norms": _np(g1),
            "change_norms": _np(change)}


def _np(d):
    return {k: np.asarray(x, np.float64) for k, x in d.items()}
