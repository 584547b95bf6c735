"""The training cell's run at smoke widths on four CPU devices: sound,
its control, and with the timed path broken underneath (the state
left unchanged, half of each chip's rows left out, the exchange between
chips left out).  Each must come out as the check says.

The runs share one subprocess, since the four devices have to be asked
for before JAX starts.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.model import BENCH

ROOT = BENCH.parent

SCRIPT = textwrap.dedent('''
    import json, sys, time
    import jax
    from bench import harness

    TINY = {"name": "tiny", "model_type": "qwen3", "hidden_size": 64,
            "intermediate_size": 128, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "vocab_size": 512, "rope_theta": 1e6,
            "rms_norm_eps": 1e-6, "tie_word_embeddings": True}
    JOB = dict(harness.load_traffic("train-zero3"), rows_per_chip=2,
               seq=128)
    LIMITS = json.loads(sys.argv[1])
    # the cell's entries, as BENCHMARK.json will hold them once the
    # cell is measured on four chips
    CELL = "qwen3-1.7b.train-zero3"
    BENCH = harness.load_benchmark()
    BENCH["workloads"].append({"name": CELL, "config": "qwen3-1.7b-14l",
                               "traffic": "train-zero3", "chips": 4})
    BENCH["end_to_end"].append({"name": "train_tok_s", "unit": "tokens/s",
                                "workloads": [CELL]})

    def run(control=False):
        return harness.run_cell(
            CELL, 5, 1.0, False, t_start=time.perf_counter(),
            require_chip=False, bench=BENCH, cfg=TINY, traffic=JOB,
            limits=LIMITS, control=control, log=lambda *a: None)

    out = {"sound": run(control=True)}

    from repro import api
    real_step = api.Trainer.step

    def unchanged(self, batch):
        state = self.state
        self.state = jax.tree_util.tree_map(lambda x: x.copy(), state)
        metrics = real_step(self, batch)
        self.state = state
        return metrics

    api.Trainer.step = unchanged
    out["unchanged"] = run()
    api.Trainer.step = real_step

    from repro.train import step as train_step
    real_loss = train_step.make_loss_fn

    def half(cfg, tc):
        f = real_loss(cfg, tc)

        def loss_fn(params, batch):
            n = batch["tokens"].shape[0] // 2
            return f(params, {"tokens": batch["tokens"][:n]})
        return loss_fn

    train_step.make_loss_fn = half
    out["half_batch"] = run()
    train_step.make_loss_fn = real_loss

    def own_shard(x, axis_name, scatter_dimension=0, tiled=True):
        n = jax.lax.psum(1, axis_name)
        per = x.shape[0] // n
        i = jax.lax.axis_index(axis_name)
        return jax.lax.dynamic_slice_in_dim(x, i * per, per)

    jax.lax.psum_scatter = own_shard
    out["no_exchange"] = run()
    print(json.dumps(out))
''')
# At these widths, seeds 5 and 6, the program (bfloat16 compute) read
# loss 1.5e-5-1.9e-5, grad 5.9e-4-2.2e-3 and change 5.8e-4-2.0e-3
# against the float32 reference; the control (float8 weights) 8.7e-5-
# 2.3e-4, 6.4e-3-2.2e-2 and 2.6e-3-9.3e-3; rows or the exchange left
# out moved the first gradient of some leaf by 0.42-0.55, and an
# unchanged state reads 1 on the change
LIMITS = {"loss_gap": {"limit": 6e-5}, "grad_gap": {"limit": 0.006},
          "change_gap": {"limit": 0.005}}


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, json.dumps(LIMITS)],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_training_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tok_s", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_training_control_is_not_correct(runs):
    """AdamW on float8 weights in the program's place."""
    assert not runs["sound"]["control"]["correct"], runs["sound"]["control"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_exchange"])
def test_broken_training_step_is_not_correct(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]
