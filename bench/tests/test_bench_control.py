"""The control at a size a test run holds: the reference computed with
float8 weights, the step below the bfloat16 the configuration states,
has to fail where the bfloat16 program passes.

At hidden size 256, two layers and a 2048-row vocabulary on the CPU,
the program's widest gap read 0.0050-0.0083 and the control's
0.093-0.125 on four seeds; the limit 0.03 lies between them.
"""
import time

from bench import harness

CFG = {"name": "small", "model_type": "qwen3", "hidden_size": 256,
       "intermediate_size": 768, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
       "vocab_size": 2048, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": True}
TRAFFIC = {"driver": "serve", "rate_per_s": 4.0, "block": 8,
           "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                          "min": 8, "max": 96},
           "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                          "min": 4, "max": 32},
           "server": {"slots": 4, "page_size": 8, "prefill_chunk": 16,
                      "decode_chunk": 4, "kv_pool_gb": 1.0},
           "check": {"min_tokens": 100, "drain_s": 30}}
LIMIT = 0.03


def test_control_fails_where_the_program_passes():
    bench = harness.load_benchmark()
    for seed in (1, 2, 3):
        out = harness.run_cell(
            "qwen3-1.7b.chat", seed, 2.0, False,
            t_start=time.perf_counter(), require_chip=False, bench=bench,
            cfg=CFG, traffic=TRAFFIC, limits={"max_gap": {"limit": LIMIT}},
            control=True, log=lambda *a: None)
        got = out["checks"]["max_gap"]["value"]
        ctl = out["control"]["checks"]["max_gap"]["value"]
        assert out["correct"] and got <= LIMIT, out
        assert not out["control"]["correct"] and ctl > LIMIT, out
        assert ctl >= 3 * got, out
