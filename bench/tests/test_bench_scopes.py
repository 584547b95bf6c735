"""The scope and span reduction (``bench.scopes``): on hand-made events
against values worked out by hand, on a CPU trace for what the program
writes into it, and on a trimmed trace recorded on a TPU v5 lite."""
import glob
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import scopes, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

# times in ns.  Device: a prefill chunk [100,400) whose loop holds a
# scan slice [100,200), a copy [200,260) and an mlp op [250,400); an op
# of no serving program [450,470); the decode loop [500,900): two
# overlapping gathers [500,600) and [580,650) of the paged read, a scan
# update [650,700), an op with no scope [700,760) and one the program's
# HLO does not list [760,800).  Host: a pump with a tick admitting one
# request and prefilling it, then a pump with a decode tick.
HAND = {
    "window": [0, 1000],
    "devices": {"/device:TPU:0": {
        "modules": [["jit_prefill_chunk_fn(7)", 100, 300],
                    ["jit_convert_element_type(3)", 450, 20],
                    ["jit_decode_loop_fn(9)", 500, 400]],
        "ops": [["%while.2", 100, 300], ["%fusion.1", 100, 100],
                ["%copy.3", 200, 60], ["%fusion.4", 250, 150],
                ["%copy.10", 450, 20],
                ["%while.2", 500, 400], ["%gather.5", 500, 100],
                ["%gather.6", 580, 70], ["%fusion.7", 650, 50],
                ["%fusion.8", 700, 60], ["%fusion.9", 760, 40]]}},
    "host": [["window", 0, 1000], ["pump", 0, 480],
             ["sched.tick", 10, 460], ["sched.admit", 10, 440],
             ["sched.prefill", 60, 390], ["sched.prefill.sync", 420, 30],
             ["deliver", 480, 10], ["pump", 490, 510],
             ["sched.tick", 495, 495], ["sched.admit", 495, 3],
             ["sched.decode", 498, 487], ["sched.decode.dispatch", 498, 7],
             ["sched.decode.sync", 505, 445],
             ["sched.decode.emit", 950, 35]],
    "hlo": {
        "jit_prefill_chunk_fn(7)": {
            "while.2": ["while", None], "fusion.1": ["fusion", "layers"],
            "copy.3": ["copy", "layers"], "fusion.4": ["fusion", "mlp"]},
        "jit_decode_loop_fn(9)": {
            "while.2": ["while", "layers"],
            "gather.5": ["gather", "attn.kv_read"],
            "gather.6": ["fusion", "attn.kv_read"],
            "fusion.7": ["fusion", "layers"], "fusion.8": ["fusion", None]}},
}
HAND["args"] = [{} for _ in HAND["host"]]


def test_reduce_by_hand():
    r = scopes.reduce(HAND)
    ns = 1e-9
    assert r["busy_s"] == pytest.approx(720 * ns)
    assert r["scope_s"]["prefill"] == pytest.approx(
        {"layers": 160 * ns, "mlp": 150 * ns})
    assert r["scope_s"]["decode"] == pytest.approx(
        {"attn.kv_read": 150 * ns, "layers": 50 * ns,
         scopes.UNSCOPED: 100 * ns})
    assert r["idle_by_span"] == pytest.approx({
        "pump": 35 * ns, "deliver": 10 * ns, "sched.admit": 53 * ns,
        "sched.prefill": 60 * ns, "sched.prefill.sync": 30 * ns,
        "sched.decode.dispatch": 2 * ns, "sched.decode.sync": 50 * ns,
        "sched.decode.emit": 35 * ns, "sched.tick": 5 * ns})
    assert r["host_idle_s"] == pytest.approx(235 * ns)
    assert scopes.decode_kv_read_share(r) == pytest.approx(100 * 150 / 400)
    assert scopes.pool_carry_share(r) == pytest.approx(100 * 210 / 720)
    assert scopes.host_idle(r) == pytest.approx(23.5)
    # the longest idle gaps, labelled by the innermost span open at
    # their middle: the program's
    assert r["idle_gaps"] == [
        ["sched.admit", pytest.approx(100 * ns)],
        ["sched.decode.emit", pytest.approx(100 * ns)],
        ["sched.prefill.sync", pytest.approx(50 * ns)],
        ["deliver", pytest.approx(30 * ns)]]


def test_nothing_to_read_without_the_programs_spans_and_scopes():
    """The parent's table: benchmark spans only and no HLO.  Every new
    metric reads nothing, and every number ``trace.reduce`` gives is
    unchanged."""
    old = {"window": HAND["window"], "devices": HAND["devices"],
           "host": [h for h in HAND["host"] if not h[0].startswith("sched.")]}
    r = scopes.reduce(old)
    assert {k: r[k] for k in trace.reduce(old)} == trace.reduce(old)
    assert r["host_idle_s"] is None
    assert set(r["scope_s"]["decode"]) == {scopes.UNSCOPED}
    for f in scopes.METRICS.values():
        assert f(r) is None


def test_innermost_span_pieces():
    host = [["window", 0, 100], ["a", 0, 50], ["b", 10, 20],
            ["c", 10, 5], ["d", 50, 0], ["e", 60, 90]]
    assert scopes.innermost_spans(host, 0, 100) == [
        (0, 10, "a"), (10, 15, "c"), (15, 30, "b"), (30, 50, "a"),
        (50, 60, scopes.OUTSIDE), (60, 100, "e")]


def test_recorded_fixture_reduces_as_before():
    """The earlier chip excerpt, recorded before the program had spans
    or scopes, through ``scopes.reduce``: the numbers of
    ``trace.reduce`` are the same."""
    tr = json.loads((DATA / "trace_chat_v5e.json").read_text())
    r = scopes.reduce(tr)
    assert {k: r[k] for k in trace.reduce(tr)} == trace.reduce(tr)
    assert r["host_idle_s"] is None


def test_no_result_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(DATA.parents[1] / "scopes.py"), "--workload",
         "qwen3-1.7b.chat", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=DATA.parents[2])
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_cpu_trace_holds_the_programs_scopes_and_spans(tmp_path):
    """What a program writes into a profiler trace, read back: host spans
    with their arguments, and each compiled instruction's innermost
    scope from the HLO in the metadata plane."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("layers"):
            with jax.named_scope("attn.kv_read"):
                y = jnp.sin(x) @ x
            y = y + 1.0
        with jax.named_scope("lm_head"):
            return y * 2.0

    x = jnp.ones((8, 8))
    step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("sched.tick", pending=3,
                                          active=1):
            step(x).block_until_ready()
    tab = scopes.load(str(tmp_path))
    assert tab["host"][0][0] == "sched.tick"
    assert tab["args"][0] == {"pending": 3, "active": 1}
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        progs = scopes.programs(f.read())
    (ins,) = [v for k, v in progs.items() if k.startswith("jit_step(")]
    by_scope = {}
    for opcode, scope in ins.values():
        by_scope.setdefault(scope, set()).add(opcode)
    assert "dot" in by_scope["attn.kv_read"]
    assert "multiply" in by_scope["lm_head"]
    assert "parameter" in by_scope[None]


# the 16 ms excerpt (seed 1301000013): the end of one decode tick of 7
# live requests, the host pulling, walking and delivering its tokens,
# and the start of the next tick
RECORDED = {"busy_s": 0.01248721, "kv_read_s": 0.001766877,
            "layers_s": 0.000795495, "unscoped_s": 0.008350915,
            "host_idle_s": 0.00343832}


def _mask(intervals, lo, hi):
    import numpy as np
    m = np.zeros(hi - lo, bool)
    for s, d in intervals:
        a, b = max(s, lo) - lo, min(s + d, hi) - lo
        if b > a:
            m[a:b] = True
    return m


def test_recorded_scoped_trace_against_a_nanosecond_mask():
    """16 ms of a traced chat window on a TPU v5 lite with the program's
    spans and scopes: each scope's self time and the idle time under
    each innermost span, counted again nanosecond by nanosecond."""
    import numpy as np
    tr = json.loads((DATA / "trace_chat_v5e_scoped.json").read_text())
    lo, hi = tr["window"]
    (dev,) = tr["devices"].values()
    r = scopes.reduce(tr)
    (mod,) = {m[0] for m in dev["modules"]}
    assert trace.program_of(mod) == "decode"
    hlo = tr["hlo"][mod]
    by_scope = {}
    for name, s, d in dev["ops"]:
        opcode, scope = hlo.get(name.lstrip("%"), (None, None))
        if opcode not in scopes.CONTAINERS:
            by_scope.setdefault(scope or scopes.UNSCOPED, []).append((s, d))
    want = {k: _mask(v, lo, hi).sum() * 1e-9 for k, v in by_scope.items()}
    assert r["scope_s"]["decode"] == pytest.approx(want, abs=1e-9)

    # innermost span: paint the spans in order of start, outer first
    label = np.full(hi - lo, -1)
    names = []
    spans = sorted((h for h in tr["host"] if h[0] != "window"),
                   key=lambda h: (h[1], -h[2]))
    for name, s, d in spans:
        a, b = max(s, lo) - lo, min(s + d, hi) - lo
        if b > a:
            label[a:b] = len(names)
        names.append(name)
    idle = ~_mask([(s, d) for _, s, d in dev["ops"]], lo, hi)
    got = {}
    for i in np.unique(label[idle]):
        key = names[i] if i >= 0 else scopes.OUTSIDE
        got[key] = got.get(key, 0) + (label[idle] == i).sum() * 1e-9
    assert r["idle_by_span"] == pytest.approx(got, abs=1e-9)

    assert r["busy_s"] == pytest.approx(RECORDED["busy_s"])
    assert r["scope_s"]["decode"]["attn.kv_read"] == pytest.approx(
        RECORDED["kv_read_s"])
    assert r["scope_s"]["decode"]["layers"] == pytest.approx(
        RECORDED["layers_s"])
    assert r["scope_s"]["decode"][scopes.UNSCOPED] == pytest.approx(
        RECORDED["unscoped_s"])
    assert r["host_idle_s"] == pytest.approx(RECORDED["host_idle_s"])
    # the tick's arguments: 7 live lanes of 32, 8 steps
    decode = [a for (n, *_), a in zip(tr["host"], tr["args"])
              if n == "sched.decode"]
    assert decode == [{"live": 7, "slots": 32, "steps": 8}] * 2
    # the longest idle gap falls under the program's spans, not `pump`
    assert r["idle_gaps"][0][0].startswith("sched.")
