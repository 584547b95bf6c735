"""Configurations by name, and seeded weights made on the device.

A configuration file (``bench/configs/<name>.json``) holds the model as
its public ``config.json`` gives it, with the keys this benchmark
changed listed under ``reduced`` and what it set itself under
``assumed``.  ``program_config`` turns it into the program's
``ModelConfig``.

Weights are the benchmark's, not the program's: every leaf of the
program's parameter tree is drawn from the seed by its path and its
layer, so the plain reference can draw any one layer again, alone,
and get the same numbers.  The values are rounded to bfloat16; those
bfloat16 values are the model that the program serves and that the
reference computes in float32.
"""
from __future__ import annotations

import json
import pathlib
import zlib
from typing import Dict

import jax
import jax.numpy as jnp

BENCH = pathlib.Path(__file__).resolve().parent
MODEL_DTYPE = jnp.bfloat16


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    with open(path) as f:
        cfg = json.load(f)
    cfg["name"] = name
    return cfg


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig

    moe = None
    if cfg.get("n_routed_experts"):
        moe = MoEConfig(
            num_experts=cfg["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            num_shared_experts=cfg.get("n_shared_experts", 0),
            d_expert=cfg["moe_intermediate_size"],
            first_dense_layers=cfg.get("first_k_dense_replace", 0),
            dense_d_ff=cfg["intermediate_size"],
            capacity_factor=float(cfg["assumed"]["capacity_factor"]))
    return ModelConfig(
        name=cfg["name"],
        family="moe" if moe else "dense",
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", 0),
        d_ff=cfg["moe_intermediate_size"] if moe else cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        qkv_bias=bool(cfg.get("attention_bias", False)),
        qk_norm=cfg.get("model_type") == "qwen3",
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        moe=moe,
        dtype="bfloat16",
        param_dtype="bfloat16")


# --------------------------------------------------------------------------
# seeded weights
# --------------------------------------------------------------------------

# input axes of each matrix, counted after any layer and expert axes:
# its fan-in is the product of their sizes
_FAN_IN_AXES = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "w_up": 1,
                "w_gate": 1, "w_down": 1}
_STD_002 = ("table", "router")


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def layer_shapes(cfg: dict, layer: int) -> Dict:
    """Shapes of one layer's weights, named as the program names them."""
    d = cfg["hidden_size"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    s = jax.ShapeDtypeStruct
    f32 = jnp.float32
    mixer = {"wq": s((d, h, hd), f32), "wk": s((d, hk, hd), f32),
             "wv": s((d, hk, hd), f32), "wo": s((h, hd, d), f32)}
    if cfg.get("attention_bias"):
        mixer.update(bq=s((h, hd), f32), bk=s((hk, hd), f32),
                     bv=s((hk, hd), f32))
    if cfg.get("model_type") == "qwen3":
        mixer.update(q_norm={"scale": s((hd,), f32)},
                     k_norm={"scale": s((hd,), f32)})

    def mlp(lead, width):
        return {"w_up": s(lead + (d, width), f32),
                "w_down": s(lead + (width, d), f32),
                "w_gate": s(lead + (d, width), f32)}

    if is_moe_layer(cfg, layer):
        E, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        ffn = {"router": s((d, E), f32), "experts": mlp((E,), fe)}
        if cfg.get("n_shared_experts"):
            ffn["shared"] = mlp((), cfg["n_shared_experts"] * fe)
    else:
        ffn = mlp((), cfg["intermediate_size"])
    return {"norm1": {"scale": s((d,), f32)}, "mixer": mixer,
            "norm2": {"scale": s((d,), f32)}, "ffn": ffn}


def is_moe_layer(cfg: dict, layer: int) -> bool:
    return bool(cfg.get("n_routed_experts")) and \
        layer >= cfg.get("first_k_dense_replace", 0)


def outer_shapes(cfg: dict) -> Dict:
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    s = jax.ShapeDtypeStruct
    out = {"embed": {"table": s((V, d), jnp.float32)},
           "final_norm": {"scale": s((d,), jnp.float32)}}
    if not cfg["tie_word_embeddings"]:
        out["unembed"] = {"table": s((V, d), jnp.float32)}
    return out


def _draw(key, name: str, shape) -> jax.Array:
    leaf = name.rsplit("/", 1)[-1]
    if leaf in ("scale",):
        return jnp.ones(shape, jnp.float32)
    if leaf in ("bq", "bk", "bv", "router_bias"):
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if leaf in _STD_002:
        std = 0.02
    else:
        n_in = _FAN_IN_AXES[leaf]
        lead = 1 if "/experts/" in f"/{name}" else 0
        fan = 1
        for s in shape[lead:lead + n_in]:
            fan *= s
        std = fan ** -0.5
    return std * jax.random.normal(k, shape, jnp.float32)


def draw_tree(key, shapes, prefix: str = "") -> Dict:
    """Draw every leaf of a shape tree; names are ``prefix`` + path."""
    def one(path, s):
        return _draw(key, prefix + _leaf_name(path), s.shape).astype(
            MODEL_DTYPE)
    return jax.tree_util.tree_map_with_path(one, shapes)


def root_key(seed: int):
    return jax.random.PRNGKey(seed % (2 ** 32))


def layer_key(seed: int, layer: int):
    return jax.random.fold_in(root_key(seed), 1 + layer)


def outer_key(seed: int):
    return jax.random.fold_in(root_key(seed), 0)


def draw_layer(cfg: dict, seed: int, layer: int):
    """One layer's weights (bfloat16), drawn alone."""
    shapes = layer_shapes(cfg, layer)
    return jax.jit(lambda k: draw_tree(k, shapes, "layer/"))(
        layer_key(seed, layer))


def draw_outer(cfg: dict, seed: int):
    shapes = outer_shapes(cfg)
    return jax.jit(lambda k: draw_tree(k, shapes))(outer_key(seed))


def program_weights(cfg: dict, pcfg, seed: int):
    """The program's whole parameter tree in bfloat16, made on the
    device in one jitted call: the same numbers as ``draw_layer`` and
    ``draw_outer``, stacked the way the program's layer scan holds
    them."""
    prefix, pattern, n_rep = pcfg.block_structure()
    n_pre, P = len(prefix), len(pattern)

    def make(root):          # the key is an argument: one compile, any seed
        tree = draw_tree(jax.random.fold_in(root, 0), outer_shapes(cfg))
        pre = {f"layer{i}": draw_tree(jax.random.fold_in(root, 1 + i),
                                      layer_shapes(cfg, i), "layer/")
               for i in range(n_pre)}
        blocks = {}
        for j in range(P):
            first = n_pre + j
            shapes = layer_shapes(cfg, first)
            blocks[f"layer{j}"] = jax.vmap(
                lambda i: draw_tree(jax.random.fold_in(root, 1 + i),
                                    shapes, "layer/"))(
                jnp.arange(n_rep) * P + first)
        tree["decoder"] = {"prefix": pre, "blocks": blocks}
        return tree
    return jax.jit(make)(root_key(seed))


def stacked_weights(cfg: dict, root, shardings=None):
    """The outer weights and every layer's stacked on a leading axis,
    in float32, for a configuration whose layers all have one shape:
    the same numbers as ``program_weights``, as the plain reference
    holds them.  ``root`` is ``root_key(seed)``; ``shardings`` (a tree
    like the result) places them, made in one jitted call."""
    shapes = layer_shapes(cfg, 0)
    n = cfg["num_hidden_layers"]
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)

    def make(root):
        outer = draw_tree(jax.random.fold_in(root, 0), outer_shapes(cfg))
        layers = jax.vmap(lambda i: draw_tree(
            jax.random.fold_in(root, 1 + i), shapes, "layer/"))(
            jnp.arange(n))
        return {"outer": f32(outer), "layers": f32(layers)}
    return jax.jit(make, out_shardings=shardings)(root)
