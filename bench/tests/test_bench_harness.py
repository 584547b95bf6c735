"""The harness on the CPU: files found by name, the contract's shape of
BENCHMARK.json, percentiles, the generator, no result without a chip,
and ``correct`` coming out false when the served path is broken."""
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import generator, harness, serve
from bench.model import BENCH, load_config

ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_cells_resolve_to_files_by_name(bench):
    for w in bench["workloads"]:
        cfg = load_config(w["config"])
        assert cfg["source"] == next(c["source"] for c in bench["configs"]
                                     if c["name"] == w["config"])
        tr = harness.load_traffic(w["traffic"])
        assert hasattr(harness.driver(tr), "Job")
        assert harness.load_limits(w["name"])
        rep = harness.reported(bench, w["name"])
        assert any(m["name"] == "setup_s" for m in rep["end_to_end"])
        assert len(rep["end_to_end"]) >= 2 and rep["per_layer"]
        for m in rep["per_layer"]:
            assert callable(__import__("bench.metrics", fromlist=["load"])
                            .load(m["name"]))


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert m["better"] == "higher"
    assert 1 <= bench["run_seconds"] <= 51


def test_new_traffic_file_is_found_without_edit():
    name = f"probe_{os.getpid()}"
    path = BENCH / "traffic" / f"{name}.json"
    mix = {"driver": "serve", "rate_per_s": 2.0, "block": 20,
           "prompt_len": {"dist": "uniform", "min": 10, "max": 20},
           "output_len": {"dist": "fixed", "value": 5}}
    path.write_text(json.dumps(mix))
    try:
        tr = harness.load_traffic(name)
        reqs = generator.take(tr, 1, 100, 20)
    finally:
        path.unlink()
    assert sum(r.due < 10.0 for r in reqs) == 20
    assert all(10 <= len(r.prompt) <= 20 and r.max_new_tokens == 5
               for r in reqs)


def test_every_seed_gets_the_same_work():
    """Each block holds the mix's own lengths and gaps; the seed draws
    their order and the tokens."""
    tr = harness.load_traffic("chat")
    n = tr["block"]
    a = generator.take(tr, 1, 1000, 3 * n)
    b = generator.take(tr, 2 ** 31 + 7, 1000, 3 * n)

    def block(reqs, k):
        part = reqs[k * n:(k + 1) * n]
        gaps = np.diff([r.due for r in part] + [(k + 1) * n / tr["rate_per_s"]])
        return (sorted(len(r.prompt) for r in part),
                sorted(r.max_new_tokens for r in part), np.sort(gaps))

    for k in range(3):
        for x, y in zip(block(a, k), block(b, k)):
            np.testing.assert_allclose(x, y, rtol=1e-9)
    assert block(a, 0)[0] == sorted(generator.quantiles(tr["prompt_len"], n))
    assert [len(r.prompt) for r in a[:n]] != [len(r.prompt) for r in b[:n]]
    assert [r.due for r in a[:n]] != [r.due for r in b[:n]]
    assert a[0].due == 0.0 and a[n].due == pytest.approx(n / tr["rate_per_s"])


class _Req:
    def __init__(self, due):
        self.due, self.prompt, self.max_new_tokens = due, [0], 4


class _Tk:
    def __init__(self, due, times):
        self.req, self.times = _Req(due), times

    @property
    def first(self):
        return self.times[0] if self.times else None


def test_failed_requests_count_as_the_longest_waits():
    """Three requests with a first token after 0.1 s and two with none:
    the median is a served one; with three of five missing it is a
    failed one's whole wait, from due (1 s) to the stop (70 s)."""
    ok = [_Tk(0.0, [0.1, 0.2]) for _ in range(3)]
    e = serve.end_to_end({"T": 10.0, "stop": 70.0,
                          "tracked": ok + [_Tk(1.0, [])] * 2})
    assert e["ttft_p50_ms"] == pytest.approx(100.0)
    assert e["_failed"] == 2 and e["_attempted"] == 5
    e = serve.end_to_end({"T": 10.0, "stop": 70.0,
                          "tracked": ok[:2] + [_Tk(1.0, [])] * 3})
    assert e["ttft_p50_ms"] == pytest.approx(69000.0)
    assert e["_failed"] == 3
    assert serve.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 95) == 10


def test_no_result_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen3-1.7b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


# --------------------------------------------------------------------------
# the whole run at smoke widths, sound and with the served path broken
# --------------------------------------------------------------------------

TINY = {"name": "tiny", "model_type": "qwen3", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True}
TRAFFIC = {"driver": "serve", "rate_per_s": 4.0, "block": 8,
           "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                          "min": 8, "max": 96},
           "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 32},
           "server": {"slots": 4, "page_size": 8, "prefill_chunk": 16,
                      "decode_chunk": 4, "kv_pool_gb": 1.0},
           "check": {"min_tokens": 40, "drain_s": 30}}
# at smoke widths bfloat16 serving puts the served token within 0.02 of
# the reference's best logit; a wrong token or a lost cache lies ~0.5
# and more below it
LIMITS = {"max_gap": {"limit": 0.1}}


def run_tiny(bench):
    return harness.run_cell("qwen3-1.7b.chat", 5, 2.0, False,
                            t_start=time.perf_counter(),
                            require_chip=False, bench=bench, cfg=TINY,
                            traffic=TRAFFIC, limits=LIMITS,
                            log=lambda *a: None)


def test_sound_run_is_correct(bench):
    out = run_tiny(bench)
    assert out["correct"], out
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert out["attempted"] == 8 and out["failed"] == 0


def test_altered_token_is_not_correct(bench, monkeypatch):
    """Every token the fused decode loop samples is shifted by one."""
    from repro.serve import scheduler
    real = scheduler.masked_sample

    def shifted(logits, key, done, pad_id, sc):
        return (real(logits, key, done, pad_id, sc=sc) + 1) % 512
    monkeypatch.setattr(scheduler, "masked_sample", shifted)
    out = run_tiny(bench)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > LIMITS["max_gap"]["limit"]


def test_unchanged_cache_is_not_correct(bench, monkeypatch):
    """Prefill and decode return the KV pool as they got it: the step's
    state is left unchanged."""
    from repro.models import attention
    monkeypatch.setattr(attention, "_paged_append",
                        lambda pool, paged, positions, new: pool)
    out = run_tiny(bench)
    assert not out["correct"]
