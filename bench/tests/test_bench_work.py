"""Work counts, peaks and the benchmark's weights against the program's
shapes and against sums worked out by hand."""
import jax
import pytest

from bench import model, peaks, work

CONFIGS = ["qwen3-1.7b", "deepseek-moe-16b"]
# DeepSeekMoE 16B's published widths (arXiv:2401.06066), cut to its
# dense layer and six MoE layers: work.py's MoE arithmetic, kept for a
# cell that the program cannot serve yet
DEEPSEEK = {"name": "deepseek-moe-16b", "model_type": "deepseek",
            "hidden_size": 2048, "intermediate_size": 10944,
            "moe_intermediate_size": 1408, "num_hidden_layers": 7,
            "num_attention_heads": 16, "num_key_value_heads": 16,
            "n_routed_experts": 64, "n_shared_experts": 2,
            "num_experts_per_tok": 6, "first_k_dense_replace": 1,
            "vocab_size": 102400, "rope_theta": 10000,
            "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
            "attention_bias": False,
            "assumed": {"capacity_factor": 10.67}}


def config(name):
    return DEEPSEEK if name == "deepseek-moe-16b" else \
        model.load_config(name)


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program(name):
    from repro.models import init_model
    cfg = config(name)
    shapes = jax.eval_shape(lambda: init_model(model.program_config(cfg),
                                               jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert work.total_params(cfg) == n


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_have_the_program_tree(name):
    """The benchmark draws every leaf the program's init makes, in the
    same shape."""
    from repro.models import init_model
    cfg = config(name)
    pcfg = model.program_config(cfg)
    want = jax.eval_shape(lambda: init_model(pcfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: model.program_weights(cfg, pcfg, 1))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape


def test_published_sizes():
    """qwen3-1.7b: 28 x 50.34 M + 311.16 M embedding = 1.72 B.  The
    7-layer deepseek-moe-16b cut: 84.02 M dense layer, 6 x 587.86 M MoE
    layers, 2 x 209.72 M embedding and head: 4.03 B."""
    assert work.total_params(model.load_config("qwen3-1.7b")) == 1720574976
    assert work.total_params(DEEPSEEK) \
        == 4030625792


def test_prefill_chunk_by_hand():
    cfg = model.load_config("qwen3-1.7b")
    d, V, L = 2048, 151936, 28
    attn = d * 16 * 128 + 2 * d * 8 * 128 + 16 * 128 * d   # 12.58 M
    mlp = 3 * d * 6144                                     # 37.75 M
    per_tok = 2 * L * (attn + mlp)
    # 32 tokens at positions 64..95: keys 65..96 for each query
    keys = sum(range(65, 97))
    flops, nbytes = work.prefill_chunk(cfg, 64, 32, last=False)
    assert flops == 32 * per_tok + 4 * 16 * 128 * keys * L
    layer_bytes = L * (attn + mlp + 2 * d + 2 * 128) + d
    kv = L * 2 * 8 * 128 * 2
    assert nbytes == 2 * layer_bytes + 32 * d * 2 + 64 * kv + 32 * kv
    f_last, b_last = work.prefill_chunk(cfg, 64, 32, last=True)
    assert f_last == flops + 2 * V * d
    assert b_last == nbytes + 2 * V * d


def test_decode_step_by_hand():
    cfg = DEEPSEEK
    d, V = 2048, 102400
    attn = 4 * d * d
    dense = 3 * d * 10944
    moe = d * 64 + 6 * 3 * d * 1408 + 3 * d * 2 * 1408
    per_tok = 2 * (7 * attn + dense + 6 * moe + V * d)
    flops, nbytes = work.decode_step(cfg, [10, 20])
    assert flops == 2 * per_tok + 4 * 16 * 128 * 30 * 7
    held = (7 * (attn + 2 * d) + dense
            + 6 * (d * 64 + 64 * 3 * d * 1408 + 3 * d * 2 * 1408))
    kv = 7 * 2 * 16 * 128 * 2
    assert nbytes == 2 * (held + d + V * d) + 2 * d * 2 + 28 * kv + 2 * kv


def test_prefill_chunks_cover_the_prompt():
    assert work.prefill_chunks(70, 32) == [(0, 32, False), (32, 32, False),
                                           (64, 6, True)]
    assert work.prefill_chunks(64, 32)[-1] == (32, 32, True)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.min_seconds(197e12, 0, "TPU v5 lite") == 1.0
    assert peaks.min_seconds(0, 819e9, "TPU v5 lite") == 1.0


def test_train_step_by_hand():
    """Two 8-token rows of the 14-layer cut: forward matmuls and head per
    token, causal attention over 1..8 keys, and the backward at twice
    the forward."""
    cfg = model.load_config("qwen3-1.7b-14l")
    d, V, L = 2048, 151936, 14
    attn = d * 16 * 128 + 2 * d * 8 * 128 + 16 * 128 * d
    mlp = 3 * d * 6144
    fwd_token = 2 * (L * (attn + mlp) + V * d)
    causal = 4 * 16 * 128 * L * sum(range(1, 9))
    assert work.train_step_flops(cfg, 2, 8) == 3 * 2 * (8 * fwd_token
                                                        + causal)
