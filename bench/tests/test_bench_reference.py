"""The plain reference against the program at smoke widths on the CPU,
in prefill (the whole sequence at once) and in paged decode (tokens
served through the continuous scheduler's page pool)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model
from bench.reference import forward

QWEN = {"name": "tiny-qwen3", "model_type": "qwen3", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True}
# DeepSeekMoE's layout at smoke widths, with the program's routing:
# the program renormalises the top-k gates (see test_published_moe_gates)
MOE = {"name": "tiny-moe", "model_type": "deepseek", "hidden_size": 64,
       "intermediate_size": 192, "moe_intermediate_size": 32,
       "num_hidden_layers": 3, "num_attention_heads": 4,
       "num_key_value_heads": 4, "n_routed_experts": 8,
       "n_shared_experts": 2, "num_experts_per_tok": 2,
       "first_k_dense_replace": 1, "norm_topk_prob": True,
       "vocab_size": 512, "rope_theta": 1e4, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": False, "assumed": {"capacity_factor": 4.0}}
# float32 on both sides: the two differ only in the order of float32
# sums (chunked vs whole softmax, expert buffers vs a dense sum), which
# moves logits of size ~1 by well under 1e-4
TOL = 1e-4
SEED = 3


def program_f32(cfg):
    pcfg = model.program_config(cfg)
    pcfg = dataclasses.replace(pcfg, dtype="float32", param_dtype="float32")
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                    model.program_weights(cfg, pcfg, SEED))
    return pcfg, params


def rows(cfg, n=3, length=40):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg["vocab_size"], length - 7 * i,
                         dtype=np.int32) for i in range(n)]


@pytest.mark.parametrize("cfg", [QWEN, MOE], ids=["qwen3", "moe"])
def test_prefill_logits_match(cfg):
    from repro.models import apply_model
    pcfg, params = program_f32(cfg)
    rs = rows(cfg)
    ref = forward.logits_at(cfg, SEED, rs, [np.arange(len(r)) for r in rs])
    for r, want in zip(rs, ref):
        got = apply_model(pcfg, params, {"tokens": jnp.asarray(r)[None]})
        got = np.asarray(got["logits"][0])
        assert np.abs(got - want).max() < TOL * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("cfg", [QWEN, MOE], ids=["qwen3", "moe"])
def test_paged_decode_tokens_are_reference_argmax(cfg):
    """Greedy tokens served through prefill chunks and the fused paged
    decode loop: each is the reference's argmax to within TOL."""
    from repro.serve import make_engine
    pcfg, params = program_f32(cfg)
    rs = rows(cfg, n=4)
    eng = make_engine(pcfg, params, batch_size=2, max_len=96,
                      dtype=jnp.float32, page_size=8, prefill_chunk=16,
                      decode_chunk=4)
    outs = eng.generate(rs, 12)
    seqs = [np.concatenate([r, o[:-1]]) for r, o in zip(rs, outs)]
    wanted = [np.arange(len(r) - 1, len(r) - 1 + len(o))
              for r, o in zip(rs, outs)]
    ref = forward.logits_at(cfg, SEED, seqs, wanted)
    for lg, o in zip(ref, outs):
        gaps = forward.greedy_gaps(lg, np.asarray(o))
        assert gaps.max() < TOL * max(1.0, np.abs(lg).max())


def test_published_moe_gates_are_not_the_programs():
    """DeepSeekMoE as published (``norm_topk_prob: false``) weights each
    routed expert by its softmax score; the program renormalises the
    top-k scores to sum to 1.  The reference follows the config, so the
    two part wherever routing matters."""
    from repro.models import apply_model
    published = dict(MOE, norm_topk_prob=False)
    pcfg, params = program_f32(published)
    r = rows(published, n=1)[0]
    want = forward.logits_at(published, SEED, [r], [np.arange(len(r))])[0]
    got = np.asarray(apply_model(pcfg, params,
                                 {"tokens": jnp.asarray(r)[None]})
                     ["logits"][0])
    assert np.abs(got - want).max() > 100 * TOL


def test_reference_weights_are_the_programs():
    """The reference draws each layer alone; the program's tree stacks
    the same numbers."""
    pcfg = model.program_config(MOE)
    tree = model.program_weights(MOE, pcfg, SEED)
    one = model.draw_layer(MOE, SEED, 2)
    stacked = tree["decoder"]["blocks"]["layer0"]
    for a, b in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(stacked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b[1]))
    dense = model.draw_layer(MOE, SEED, 0)
    for a, b in zip(jax.tree_util.tree_leaves(dense),
                    jax.tree_util.tree_leaves(
                        tree["decoder"]["prefix"]["layer0"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
