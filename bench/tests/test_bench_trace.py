"""The reduction from trace to metrics, on hand-made events and on a
trimmed trace recorded on a TPU v5 lite, against values worked out by
hand."""
import json
import pathlib

import pytest

from bench import metrics, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

# times in ns.  Device: ops A [100,300) and B [250,400) overlap, an
# all-gather [350,500) is hidden by B up to 400, C [600,700).  Programs:
# a prefill chunk [100,400), the decode loop [600,700).  Host: one pump
# [50,550), then waiting [550,1000).
HAND = {
    "window": [0, 1000],
    "devices": {"/device:TPU:0": {
        "modules": [["jit_prefill_chunk_fn(3)", 100, 300],
                    ["jit_other", 350, 150],
                    ["jit_decode_loop_fn", 600, 100]],
        "ops": [["fusion.1", 100, 200], ["fusion.2", 250, 150],
                ["all-gather.3", 350, 150], ["fusion.1", 600, 100]]}},
    "host": [["window", 0, 1000], ["pump", 50, 500], ["wait", 550, 450]],
}


def test_reduce_by_hand():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(500e-9)      # [100,500) + [600,700)
    assert r["program_s"]["prefill"] == pytest.approx(300e-9)
    assert r["program_s"]["decode"] == pytest.approx(100e-9)
    assert r["collective_s"] == pytest.approx(150e-9)
    assert r["collective_exposed_s"] == pytest.approx(100e-9)   # [400,500)
    assert [g[0] for g in r["idle_gaps"]] == ["wait", "pump", "wait"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [300e-9, 100e-9, 100e-9])
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]


def test_readers_by_hand():
    ctx = {"trace": trace.reduce(HAND), "peak_flops": 1e12,
           "work": {"prefill": {"flops": 150.0, "min_s": 200e-9},
                    "decode": {"flops": 0.0, "min_s": 0.0}}}
    assert metrics.load("device_idle.chat")(ctx) == pytest.approx(50.0)
    assert metrics.load("prefill_share.chat")(ctx) == pytest.approx(60.0)
    assert metrics.load("prefill_roofline.chat")(ctx) == pytest.approx(
        100 * 200 / 300)
    assert metrics.load("prefill_mfu.chat")(ctx) == pytest.approx(
        100 * 150 / (300e-9 * 1e12))
    # nothing to read: no decode work, no trace
    assert metrics.load("decode_roofline.chat")(ctx) is None
    assert metrics.load("device_idle.chat")({"trace": None}) is None


def test_union_and_subtract():
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert trace._subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


# the 12 ms excerpt (seed 104): the end of one decode tick, the host
# delivering its tokens, and the start of the next tick
RECORDED = {"window_s": 0.012, "busy_s": 0.008763888, "programs_s": 0.0087642,
            "idle_s": 0.003236096}


def _mask(events, lo, hi):
    import numpy as np
    m = np.zeros(hi - lo, bool)
    for _, s, d in events:
        a, b = max(s, lo) - lo, min(s + d, hi) - lo
        if b > a:
            m[a:b] = True
    return m


def test_recorded_trace_against_a_nanosecond_mask():
    """12 ms of a traced chat window on a TPU v5 lite (trimmed from the
    chip's trace): busy time, idle share and per-program time, counted
    again nanosecond by nanosecond, and as read off the events."""
    tr = json.loads((DATA / "trace_chat_v5e.json").read_text())
    lo, hi = tr["window"]
    (dev,) = tr["devices"].values()
    r = trace.reduce(tr)
    busy = _mask(dev["ops"], lo, hi).sum() * 1e-9
    assert r["busy_s"] == pytest.approx(busy, abs=1e-9)
    for prog in ("prefill", "decode"):
        mods = [e for e in dev["modules"] if trace.program_of(e[0]) == prog]
        want = _mask(mods, lo, hi).sum() * 1e-9
        assert r["program_s"].get(prog, 0.0) == pytest.approx(want, abs=1e-9)
    assert r["collective_s"] == 0.0          # one chip: no collectives
    assert r["window_s"] == pytest.approx(RECORDED["window_s"])
    assert r["busy_s"] == pytest.approx(RECORDED["busy_s"])
    assert sum(r["program_s"].values()) == pytest.approx(
        RECORDED["programs_s"])
    # the host finishing the tick's pump while the device waits
    assert r["idle_gaps"][0] == ["pump", pytest.approx(RECORDED["idle_s"])]
