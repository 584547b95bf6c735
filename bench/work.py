"""Operations and bytes that the algorithm needs, from a config's shapes.

Counts are of what a step has to do, not of what the program happens to
do: a prefill chunk that is not a prompt's last needs no logits, a
decode step reads each weight once and the keys and values of the live
contexts only, and a MoE token runs its top-k routed experts and the
shared ones.  Bytes are of bfloat16 weights and KV cache.

A multiply-add counts as two operations.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from bench.model import is_moe_layer

ITEMSIZE = 2          # bfloat16


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, hk, hd


def layer_params(cfg: dict, layer: int) -> Dict[str, int]:
    """Parameters of one layer by part."""
    d, h, hk, hd = _dims(cfg)
    attn = d * h * hd + 2 * d * hk * hd + h * hd * d
    if cfg.get("attention_bias"):
        attn += (h + 2 * hk) * hd
    norms = 2 * d + (2 * hd if cfg.get("model_type") == "qwen3" else 0)
    out = {"attn": attn, "norms": norms}
    if is_moe_layer(cfg, layer):
        fe, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        out["router"] = d * E
        out["experts"] = E * 3 * d * fe
        out["shared"] = 3 * d * cfg.get("n_shared_experts", 0) * fe
    else:
        out["mlp"] = 3 * d * cfg["intermediate_size"]
    return out


def outer_params(cfg: dict) -> Dict[str, int]:
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": V * d, "final_norm": d}
    if not cfg["tie_word_embeddings"]:
        out["head"] = V * d
    return out


def total_params(cfg: dict) -> int:
    return (sum(outer_params(cfg).values())
            + sum(sum(layer_params(cfg, i).values())
                  for i in range(cfg["num_hidden_layers"])))


def _layer_active_matmul(cfg: dict, layer: int) -> int:
    """Matmul parameters one token multiplies through in a layer."""
    p = layer_params(cfg, layer)
    if "experts" in p:
        fe = cfg["moe_intermediate_size"]
        ffn = (p["router"] + cfg["num_experts_per_tok"] * 3
               * cfg["hidden_size"] * fe + p["shared"])
    else:
        ffn = p["mlp"]
    return p["attn"] + ffn


def token_flops(cfg: dict, head: bool) -> int:
    """Matmul operations for one token through every layer, plus the
    head when its logits are needed."""
    n = sum(_layer_active_matmul(cfg, i)
            for i in range(cfg["num_hidden_layers"]))
    if head:
        n += cfg["vocab_size"] * cfg["hidden_size"]
    return 2 * n


def attention_flops(cfg: dict, keys: int) -> int:
    """Scores and weighted values for one query over ``keys`` keys, all
    layers: 2 operations each for q.k and p.v per head dimension."""
    d, h, hk, hd = _dims(cfg)
    return 4 * h * hd * keys * cfg["num_hidden_layers"]


def kv_bytes_per_token(cfg: dict) -> int:
    d, h, hk, hd = _dims(cfg)
    return cfg["num_hidden_layers"] * 2 * hk * hd * ITEMSIZE


def weight_bytes(cfg: dict, head: bool) -> int:
    """Bytes of weights a step reads: every layer, the final norm, and
    the head table when logits are needed (the embedding is read by row,
    counted per token)."""
    n = sum(sum(layer_params(cfg, i).values())
            for i in range(cfg["num_hidden_layers"]))
    n += cfg["hidden_size"]
    if head:
        n += cfg["vocab_size"] * cfg["hidden_size"]
    return n * ITEMSIZE


def prefill_chunk(cfg: dict, start: int, length: int,
                  last: bool) -> Tuple[int, int]:
    """(operations, bytes) of one prefill chunk of ``length`` tokens at
    positions start..start+length-1; the last chunk of a prompt also
    computes the logits of its final position."""
    keys = sum(start + i + 1 for i in range(length))
    flops = (length * token_flops(cfg, head=False)
             + attention_flops(cfg, keys)
             + (2 * cfg["vocab_size"] * cfg["hidden_size"] if last else 0))
    kv = kv_bytes_per_token(cfg)
    nbytes = (weight_bytes(cfg, head=last)
              + length * cfg["hidden_size"] * ITEMSIZE     # embedding rows
              + start * kv                                 # keys read
              + length * kv)                               # keys written
    return flops, nbytes


def prefill_chunks(prompt: int, chunk: int) -> List[Tuple[int, int, bool]]:
    """The (start, length, last) chunks a prompt is prefilled in."""
    starts = list(range(0, prompt, chunk))
    return [(s, min(chunk, prompt - s), s == starts[-1]) for s in starts]


def decode_step(cfg: dict, keys: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of one decode step in which each live slot
    attends over ``keys`` keys (its context, the new token included)."""
    keys = list(keys)
    n = len(keys)
    kv = kv_bytes_per_token(cfg)
    flops = n * token_flops(cfg, head=True) + sum(
        attention_flops(cfg, k) for k in keys)
    nbytes = (weight_bytes(cfg, head=True)
              + n * cfg["hidden_size"] * ITEMSIZE
              + sum(k - 1 for k in keys) * kv + n * kv)
    return flops, nbytes


def train_step_flops(cfg: dict, rows: int, seq: int) -> int:
    """Operations of one training step on ``rows`` sequences of ``seq``
    tokens: the forward pass (every matmul and the head for each token,
    causal attention over the keys up to each position) and the
    backward pass at twice the forward; recomputation is not counted."""
    causal = attention_flops(cfg, seq * (seq + 1) // 2)
    return 3 * rows * (seq * token_flops(cfg, head=True) + causal)
