"""The serving path traced from inside: the scheduler's host spans in a
profiler trace, the queue-wait and occupancy counters, and the named
scopes in the compiled serving programs' ``op_name`` metadata."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import init_model
from repro.serve import ContinuousScheduler, scheduler
from repro.serve.scheduler import SCOPES, SPANS

KEY = jax.random.PRNGKey(3)


def _cfg(arch="qwen3-1.7b", **kw):
    return smoke_config(arch).with_overrides(dtype="float32", **kw)


def _sched(cfg, params, **kw):
    base = dict(slots=2, max_len=64, page_size=8, prefill_chunk=8,
                decode_chunk=4, num_pages=32)
    base.update(kw)
    return ContinuousScheduler(cfg, params, **base)


def _prompt(seed, n, vocab):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, vocab))


@pytest.fixture(scope="module")
def params():
    return init_model(_cfg(), KEY)


def _staggered(sch, vocab):
    """Two requests, the second submitted after the first has decoded:
    drive ticks by hand and return the outputs by uid."""
    a = sch.submit(_prompt(0, 19, vocab), 10)
    sch.tick()
    sch.tick()
    b = sch.submit(_prompt(1, 11, vocab), 7)
    while sch.tick():
        pass
    res = sch.take_results()
    return {u: np.asarray(res[u].out, np.int32) for u in (a, b)}


def _spans(trace_dir):
    """(name, args) of every scheduler span in the trace's host planes."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    out.append((e.name, dict(e.stats)))
    return out


def test_spans_under_the_profiler(params, tmp_path):
    """Every span is written, ``sched.decode``'s arguments add up to the
    occupancy counter, and tracing adds no sync, no dispatch and changes
    no token."""
    cfg = _cfg()
    plain = _sched(cfg, params)
    want = _staggered(plain, cfg.vocab_size)

    traced = _sched(cfg, params)
    with jax.profiler.trace(str(tmp_path)):
        got = _staggered(traced, cfg.vocab_size)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    st, st0 = traced.stats(), plain.stats()
    for k in ("host_syncs", "dispatches", "decode_live_lane_steps",
              "decode_lane_steps", "admitted"):
        assert st[k] == st0[k], k

    spans = _spans(str(tmp_path))
    assert {n for n, _ in spans} == set(SPANS)
    decode = [a for n, a in spans if n == "sched.decode"]
    assert len(decode) == st["decode_dispatches"]
    assert sum(a["live"] * a["steps"] for a in decode) \
        == st["decode_live_lane_steps"]
    assert sum(a["slots"] * a["steps"] for a in decode) \
        == st["decode_lane_steps"]
    prefill = [a for n, a in spans if n == "sched.prefill"]
    assert sorted(a["prompt_len"] for a in prefill) == [11, 19]
    assert sum(a["chunks"] for a in prefill) == st["prefill_dispatches"]
    assert all(a["queued_ms"] >= 0 for a in prefill)
    assert sum(a["admitted"] for n, a in spans if n == "sched.admit") == 2
    n_ticks = sum(1 for n, _ in spans if n == "sched.tick")
    assert n_ticks == sum(1 for n, _ in spans if n == "sched.admit")
    assert sum(1 for n, _ in spans if n == "sched.prefill.sync") == 2
    for sub in ("dispatch", "sync", "emit"):
        assert sum(1 for n, _ in spans
                   if n == f"sched.decode.{sub}") == len(decode)


class _Clock:
    """Stands in for the scheduler's ``time`` module: a clock the test
    sets by hand."""
    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        return self.t


def test_queue_wait_and_occupancy_by_hand(params, monkeypatch):
    """Two slots, decode ticks of 4 steps.  A (5 tokens) and B (13) are
    admitted at t=1; C (5) is held by the full batch until A retires,
    and admitted at t=2.  Decode ticks: A+B, B+C, B alone."""
    clock = _Clock()
    monkeypatch.setattr(scheduler, "time", clock)
    cfg = _cfg()
    sch = _sched(cfg, params)
    uids = [sch.submit(_prompt(i, 9, cfg.vocab_size), n)
            for i, n in enumerate((5, 13, 5))]
    for t in (1.0, 2.0, 3.0):
        clock.t = t
        more = sch.tick()
    assert not more
    st = sch.stats()
    assert st["admitted"] == 3
    assert st["queue_wait_s_sum"] == pytest.approx(1.0 + 1.0 + 2.0)
    assert st["decode_lane_steps"] == 3 * 2 * 4
    assert st["decode_live_lane_steps"] == (2 + 2 + 1) * 4
    assert st["decode_occupancy"] == pytest.approx(20 / 24)
    res = sch.take_results()
    assert [len(res[u].out) for u in uids] == [5, 13, 5]
    assert [res[u].ttft for u in uids] == [1.0, 1.0, 2.0]


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _scopes_in(names):
    return {part for n in names for part in n.split("/") if part in SCOPES}


@pytest.mark.parametrize("arch,kernel,mixer", [
    ("qwen3-1.7b", "xla", "mlp"),
    ("qwen3-1.7b", "pallas", "mlp"),
    ("deepseek-v3-671b", "xla", "moe"),
])
def test_serving_programs_carry_the_scopes(arch, kernel, mixer):
    """The compiled decode loop and last prefill chunk name every part
    of the model step in their ``op_name`` metadata, on GQA and MLA
    layers and with either paged read."""
    cfg = _cfg(arch, decode_kernel=kernel)
    sch = _sched(cfg, init_model(cfg, KEY))
    decode = _op_names(sch.lower_decode().compile().as_text())
    prefill = _op_names(sch._prefill_last_fn.lower(
        sch.params, sch.kv.slot_cache(0), sch.kv.table([0]),
        jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
        KEY).compile().as_text())
    want = {"embed", "layers", "layer", "attn.qkv", "attn.kv_write",
            "attn.kv_read", "attn.out", mixer, "lm_head", "sample"}
    if kernel == "xla":             # the kernel computes the core itself
        want.add("attn.core")
    for names in (decode, prefill):
        assert want <= _scopes_in(names)
        # the layer scan's own ops sit in `layers`, outside any `layer`
        assert any("layers" in n.split("/") and "layer" not in n.split("/")
                   for n in names)
    # the programs keep their functions' names
    assert any(n.startswith("jit(decode_loop_fn)") for n in decode)
    assert any(n.startswith("jit(prefill_last_fn)") for n in prefill)


MOE_SCOPES = {"moe", "moe.route", "moe.dispatch", "moe.experts",
              "moe.combine", "moe.shared"}


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "deepseek-v3-671b"])
def test_decode_loop_carries_the_moe_scopes(arch):
    """The compiled decode loop of a MoE config splits the layer's
    ``moe`` into its route, dispatch, experts, combine and shared parts
    (softmax and sigmoid routers alike)."""
    cfg = _cfg(arch)
    sch = _sched(cfg, init_model(cfg, KEY))
    names = _op_names(sch.lower_decode().compile().as_text())
    assert MOE_SCOPES <= set(SCOPES)
    assert MOE_SCOPES <= _scopes_in(names)
