"""The closed-loop batch driver on the CPU: DeepSeekMoE's published
routing (``norm_topk_prob`` false) through the driver's config path
against the plain reference, in prefill and in paged decode; the
closed loop's clients; its end-to-end metric; and ``correct`` over a
whole run at smoke widths."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import batch, harness, model, serve
from bench.reference import forward

# DeepSeekMoE's layout at smoke widths, as published: the routed experts
# weighted by their softmax scores, not renormalised over the top k (2
# of 16 chosen, near the published 6 of 64); capacity E/k, as the cell's
MOE = {"name": "tiny-moe", "model_type": "deepseek", "hidden_size": 64,
       "intermediate_size": 192, "moe_intermediate_size": 32,
       "num_hidden_layers": 3, "num_attention_heads": 4,
       "num_key_value_heads": 4, "n_routed_experts": 16,
       "n_shared_experts": 2, "num_experts_per_tok": 2,
       "first_k_dense_replace": 1, "norm_topk_prob": False,
       "vocab_size": 512, "rope_theta": 1e4, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": False, "assumed": {"capacity_factor": 8.0}}
# float32 on both sides: the two differ only in the order of float32
# sums (chunked vs whole softmax, expert buffers vs a dense sum), which
# moves logits of size ~1 by well under 1e-4
TOL = 1e-4
SEED = 3


def program_f32(cfg):
    pcfg = dataclasses.replace(batch.program_config(cfg), dtype="float32",
                               param_dtype="float32")
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                    model.program_weights(cfg, pcfg, SEED))
    return pcfg, params


def rows(cfg, n=3, length=40):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg["vocab_size"], length - 7 * i,
                         dtype=np.int32) for i in range(n)]


def test_config_path_keeps_the_published_gates():
    """The driver serves the file's gates; at full size its program is
    the repository's deepseek-moe-16b cut to the cell's 7 layers (the
    one ``tests/test_tpu_compile.py`` compiles for the chip), with
    bfloat16 weights."""
    from repro.configs import get_config
    assert batch.program_config(MOE).moe.norm_topk_prob is False
    assert model.program_config(MOE).moe.norm_topk_prob is True
    got = batch.program_config(model.load_config("deepseek-moe-16b"))
    want = get_config("deepseek-moe-16b")
    want = want.with_overrides(
        num_layers=7, param_dtype="bfloat16",
        moe=dataclasses.replace(want.moe, capacity_factor=10.67))
    assert dataclasses.replace(got, name=want.name) == want


def test_prefill_logits_match():
    from repro.models import apply_model
    pcfg, params = program_f32(MOE)
    rs = rows(MOE)
    ref = forward.logits_at(MOE, SEED, rs, [np.arange(len(r)) for r in rs])
    for r, want in zip(rs, ref):
        got = apply_model(pcfg, params, {"tokens": jnp.asarray(r)[None]})
        got = np.asarray(got["logits"][0])
        assert np.abs(got - want).max() < TOL * max(1.0, np.abs(want).max())


def test_paged_decode_tokens_are_reference_argmax():
    """Greedy tokens served through prefill chunks and the fused paged
    decode loop: each is the reference's argmax to within TOL."""
    from repro.serve import make_engine
    pcfg, params = program_f32(MOE)
    rs = rows(MOE, n=4)
    eng = make_engine(pcfg, params, batch_size=2, max_len=96,
                      dtype=jnp.float32, page_size=8, prefill_chunk=16,
                      decode_chunk=4)
    outs = eng.generate(rs, 12)
    seqs = [np.concatenate([r, o[:-1]]) for r, o in zip(rs, outs)]
    wanted = [np.arange(len(r) - 1, len(r) - 1 + len(o))
              for r, o in zip(rs, outs)]
    ref = forward.logits_at(MOE, SEED, seqs, wanted)
    for lg, o in zip(ref, outs):
        gaps = forward.greedy_gaps(lg, np.asarray(o))
        assert gaps.max() < TOL * max(1.0, np.abs(lg).max())


# --------------------------------------------------------------------------
# the closed loop at smoke widths
# --------------------------------------------------------------------------

TRAFFIC = {"driver": "batch", "clients": 6, "block": 4,
           "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
           "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.3,
                          "min": 6, "max": 24},
           "server": {"slots": 3, "page_size": 8, "prefill_chunk": 16,
                      "decode_chunk": 4, "kv_pool_gb": 1.0},
           "check": {"min_tokens": 200, "drain_s": 30}}
# at smoke widths bfloat16 serving puts the served token within 0.01 of
# the reference's best logit; gates renormalised over the top k put it
# 0.30-0.34 below (seeds 7-9)
LIMITS = {"max_gap": {"limit": 0.1}}


@pytest.fixture(scope="module")
def job():
    j = batch.Job(MOE, TRAFFIC, 7, 2.0, jax.devices()[:1],
                  lambda *a: None)
    j.run()
    j.release()
    return j


def test_requests_are_the_same_work_in_another_order():
    n = TRAFFIC["block"]
    a = batch.requests(TRAFFIC, 1, 512)
    b = batch.requests(TRAFFIC, 2 ** 31 + 7, 512)
    a = [next(a) for _ in range(3 * n)]
    b = [next(b) for _ in range(3 * n)]
    for k in range(3):
        for attr in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
            assert sorted(map(attr, a[k * n:(k + 1) * n])) \
                == sorted(map(attr, b[k * n:(k + 1) * n]))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_closed_loop_keeps_the_clients_in_flight(job):
    """Every pump starts with exactly ``clients`` requests in flight, and
    each request that finished was followed by a new one."""
    w = job.window
    clients = TRAFFIC["clients"]
    assert w["in_flight"] and set(w["in_flight"]) == {clients}
    finished = sum(t.done for t in w["tracked"])
    assert finished > clients
    assert len(w["tracked"]) == clients + finished
    # all sent at the window's start, then one at each completion
    assert [t.req.due for t in w["tracked"][:clients]] == [0.0] * clients
    ends = sorted(t.times[-1] for t in w["tracked"] if t.done)
    assert sorted(t.req.due for t in w["tracked"][clients:]) == ends


def test_end_to_end_is_the_serving_drivers(job):
    got = job.end_to_end()
    want = serve.end_to_end(job.window)
    assert got["itl_p95_ms"] == want["itl_p95_ms"]
    assert got["_gaps"] > 0 and got["_failed"] == 0


def test_sound_run_is_correct(job):
    res = job.check(LIMITS)
    assert harness.passed(res["checks"]), res


def test_renormalised_gates_are_not_correct(monkeypatch):
    """The program computing the renormalised top-k gates, as it did
    before ``norm_topk_prob``, serves another model: ``correct`` fails."""
    monkeypatch.setattr(batch, "program_config", model.program_config)
    out = harness.run_cell("deepseek-moe-16b.batch", 7, 2.0, False,
                           t_start=time.perf_counter(), require_chip=False,
                           cfg=MOE, traffic=TRAFFIC, limits=LIMITS,
                           log=lambda *a: None)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > LIMITS["max_gap"]["limit"]
