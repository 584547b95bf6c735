"""Where inside the serving program a traced window's time went.

``load`` reads the profiler's ``.xplane.pb`` into the event table of
``bench.trace`` (the same keys and entries, so ``bench.trace.reduce``
reads it unchanged) with three additions:

- ``host`` also holds the program's own spans (``SPANS`` of
  ``repro.serve.scheduler``); ``args`` holds each host span's
  arguments, in the same order.  ``trace.reduce`` then labels each idle
  gap by the innermost open span, the program's where one is open;
- ``hlo``: for each compiled program, under the module name its device
  events carry, each instruction's opcode and innermost named scope
  (``SCOPES`` of the scheduler, or ``None``).  The device events carry
  only an instruction's name; its ``op_name`` metadata comes from the
  optimized HLO the profiler keeps in its ``/host:metadata`` plane.

    {"window": ..., "devices": ..., "host": [[name, start_ns, dur_ns]],
     "args": [{key: value}], "hlo": {module: {instruction: [opcode,
     scope]}}}

``reduce`` adds to ``trace.reduce``'s result each serving program's
device time by innermost scope and the idle time by innermost open host
span; ``decode_kv_read_share``, ``pool_carry_share`` and ``host_idle``
read it.  Run alone, this module traces one served cell and prints
them:

    python3 bench/scopes.py --workload qwen3-1.7b.chat --seed <n> \\
        --seconds 51
"""
from __future__ import annotations

import bisect
import glob
import os
import pathlib
import sys
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

if __name__ == "__main__":
    ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

try:
    from repro.serve.scheduler import SCOPES, SPANS as PROGRAM_SPANS
except ImportError:                     # a program without them: no scopes
    SCOPES, PROGRAM_SPANS = (), ()

# instructions whose device event spans the ops of the computations they
# run: their time is their children's
CONTAINERS = ("while", "conditional", "call")
UNSCOPED = "(unscoped)"
OUTSIDE = "outside spans"


# --------------------------------------------------------------------------
# protobuf wire format, for the few fields read from the trace
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) pairs of one encoded message: varints as
    ints, length-delimited fields as memoryviews."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {kind} in a profiler trace")
        yield key >> 3, val


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def innermost_scope(op_name: str) -> Optional[str]:
    """The last component of an ``op_name`` path that is a scope name."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def _instructions(hlo_proto) -> Dict[str, list]:
    """{instruction: [opcode, innermost scope]} of a serialized
    ``xla.HloProto`` (hlo_module 1 > computations 3 > instructions 2 >
    name 1, opcode 2, metadata 7 > op_name 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            for f, ins in _fields(comp):
                if f != 2:
                    continue
                name = opcode = op_name = ""
                for g, v in _fields(ins):
                    if g == 1:
                        name = _str(v)
                    elif g == 2:
                        opcode = _str(v)
                    elif g == 7:
                        for h, w in _fields(v):
                            if h == 2:
                                op_name = _str(w)
                out[name] = [opcode, innermost_scope(op_name)]
    return out


def programs(xspace: bytes) -> Dict[str, Dict[str, list]]:
    """{module name: {instruction: [opcode, scope]}} for every program
    whose optimized HLO the trace's ``/host:metadata`` plane holds
    (XSpace planes 1 > XPlane name 2, event_metadata 4, stat_metadata 5;
    XEventMetadata name 2, stats 5 > XStat metadata_id 1, bytes 6)."""
    out: Dict[str, Dict[str, list]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and _str(v) == "/host:metadata"
                   for k, v in fields):
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:                  # map entry: key 1, XStatMetadata 2
                md = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[md.get(1, 0)] = _str(md.get(2, b""))
        for k, entry in fields:
            if k != 4:
                continue
            md = dict(_fields(entry)).get(2, b"")
            name, protos = "", []
            for g, v in _fields(md):
                if g == 2:
                    name = _str(v)
                elif g == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1, 0)) == "Hlo Proto" \
                            and 6 in st:
                        protos.append(st[6])
            for p in protos:
                out[name] = _instructions(p)
    return out


# --------------------------------------------------------------------------
# load
# --------------------------------------------------------------------------

def load(trace_dir: str) -> dict:
    """``trace.load`` of the newest ``.xplane.pb`` under ``trace_dir``,
    with the program's spans, their arguments and its programs' HLO."""
    from jax.profiler import ProfileData

    tab = trace.load(trace_dir)
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    with open(path, "rb") as f:
        raw = f.read()
    tab["args"] = [{} for _ in tab["host"]]
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in PROGRAM_SPANS:
                        tab["host"].append([e.name, int(e.start_ns),
                                            int(e.duration_ns)])
                        tab["args"].append({k: v for k, v in e.stats})
    ran = {m[0] for d in tab["devices"].values() for m in d["modules"]}
    tab["hlo"] = {k: v for k, v in programs(raw).items() if k in ran}
    return tab


# --------------------------------------------------------------------------
# reduce
# --------------------------------------------------------------------------

def innermost_spans(host, lo: int, hi: int) -> List[Tuple[int, int, str]]:
    """[lo, hi) cut into pieces, each labelled by the innermost open
    host span (the latest started of those open), or ``OUTSIDE``."""
    bounds = []
    for i, (name, s, d) in enumerate(host):
        if name != "window" and d > 0:
            bounds.append((s, 1, -(s + d), i))   # outer starts first
            bounds.append((s + d, 0, -s, i))     # inner ends first
    bounds.sort()
    stack: List[int] = []
    out, t = [], lo
    for x, starts, _, i in bounds:
        x = min(max(x, lo), hi)
        if x > t:
            out.append((t, x, host[stack[-1]][0] if stack else OUTSIDE))
            t = x
        if starts:
            stack.append(i)
        else:
            stack.remove(i)
    if t < hi:
        out.append((t, hi, OUTSIDE))
    return out


def _intersect(a_ivs, pieces):
    """Length of the (unioned, sorted) ``a_ivs`` under each label of the
    sorted, disjoint ``pieces``."""
    out: Dict[str, int] = defaultdict(int)
    j = 0
    for a, b in a_ivs:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            if hi > lo:
                out[pieces[k][2]] += hi - lo
            k += 1
    return out


def reduce(tr: dict) -> dict:
    """``trace.reduce`` of the table, and besides:

    - ``scope_s``: per serving program (``trace.PROGRAMS``), each
      innermost scope's self time: the union of its operations' device
      intervals in the window, container operations left out; operations
      with no scope count under ``UNSCOPED``;
    - ``idle_by_span``: the device's idle time by innermost open host
      span;
    - ``host_idle_s``: the part of it under a program span, or ``None``
      where the window holds no program span or no device."""
    out = trace.reduce(tr)
    lo, hi = tr["window"]
    hlo = tr.get("hlo", {})
    pieces = innermost_spans(tr["host"], lo, hi)
    scope_ns: Dict[str, Dict[str, int]] = defaultdict(dict)
    idle_ns: Dict[str, int] = defaultdict(int)
    for dev in tr["devices"].values():
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        by_scope: Dict[tuple, list] = defaultdict(list)
        for name, s, d in dev["ops"]:
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= mods[k][1] + mods[k][2]:
                continue
            prog = trace.program_of(mods[k][0])
            if prog is None:
                continue
            opcode, scope = hlo.get(mods[k][0], {}).get(
                name.lstrip("%"), (None, None))
            if opcode in CONTAINERS:
                continue
            by_scope[(prog, scope or UNSCOPED)].append([name, s, d])
        for (prog, scope), evs in by_scope.items():
            ns = trace._length(trace.union(trace._clip(evs, lo, hi)))
            scope_ns[prog][scope] = scope_ns[prog].get(scope, 0) + ns
        busy = trace.union(trace._clip(dev["ops"] or dev["modules"], lo, hi))
        for label, ns in _intersect(trace._subtract([(lo, hi)], busy),
                                    pieces).items():
            idle_ns[label] += ns
    n = max(1, len(tr["devices"]))
    program_spans = tr["devices"] and any(h[0] in PROGRAM_SPANS
                                          for h in tr["host"])
    out["scope_s"] = {p: {k: v * 1e-9 / n for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])} for p, d in scope_ns.items()}
    out["idle_by_span"] = {k: v * 1e-9 / n for k, v in sorted(
        idle_ns.items(), key=lambda kv: -kv[1])}
    out["host_idle_s"] = (sum(v for k, v in out["idle_by_span"].items()
                              if k in PROGRAM_SPANS)
                          if program_spans else None)
    return out


# --------------------------------------------------------------------------
# per-layer metrics of the reduced window
# --------------------------------------------------------------------------

def decode_kv_read_share(red: dict) -> Optional[float]:
    """% of the decode program's device time in ``attn.kv_read``."""
    s = red.get("scope_s", {}).get("decode", {}).get("attn.kv_read")
    total = red["program_s"].get("decode")
    return 100.0 * s / total if s and total else None


def pool_carry_share(red: dict) -> Optional[float]:
    """% of busy device time that the serving programs spend in ops
    whose innermost scope is ``layers``: the layer scan's own slices,
    updates and copies of the per-layer state, outside any layer."""
    s = [red.get("scope_s", {}).get(p, {}).get("layers")
         for p in trace.PROGRAMS]
    if not any(s) or not red["busy_s"]:
        return None
    return 100.0 * sum(x for x in s if x) / red["busy_s"]


def host_idle(red: dict) -> Optional[float]:
    """% of the traced window in which the device is idle while the
    innermost open host span is the program's."""
    if red.get("host_idle_s") is None or not red["window_s"]:
        return None
    return 100.0 * red["host_idle_s"] / red["window_s"]


METRICS = {"decode_kv_read_share.chat": decode_kv_read_share,
           "pool_carry_share.chat": pool_carry_share,
           "host_idle.chat": host_idle}


# --------------------------------------------------------------------------
# one traced cell, run alone
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    """Trace one served cell's window as ``bench/run.py --trace 1``
    does and print, as the last line, the cell's per-layer metrics, the
    three above, the traced window's ``itl_p95_ms``, and the breakdown
    by scope and by span.  No check of the output."""
    import argparse
    import json
    import shutil
    import tempfile
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, metrics, model, peaks

    bench = harness.load_benchmark()
    w = harness.workload(bench, args.workload)
    try:
        devs = harness.require_devices(w["chips"])[:w["chips"]]
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    harness.compile_cache()
    # the persistent cache's key leaves out op metadata: without this, a
    # program compiled before a scope was added or renamed is loaded
    # with its old names, and the scopes read from the trace are stale
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    traffic = harness.load_traffic(w["traffic"])
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    job = harness.driver(traffic).Job(model.load_config(w["config"]),
                                      traffic, args.seed, args.seconds,
                                      devs, log)
    setup_s = time.perf_counter() - t_start
    tmp = tempfile.mkdtemp(prefix="bench_scopes_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    job.run(annotate=jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    red = reduce(load(tmp))
    shutil.rmtree(tmp, ignore_errors=True)
    kind = devs[0].device_kind
    ctx = {"trace": red, "peak_flops": peaks.peaks(kind)["bf16_flops"],
           "work": job.work(kind)}
    vals = {m["name"]: metrics.load(m["name"])(ctx)
            for m in harness.reported(bench, args.workload)["per_layer"]}
    vals.update({k: f(red) for k, f in METRICS.items()})
    e2e = job.end_to_end()
    print(json.dumps({
        "metrics": vals, "itl_p95_ms": e2e["itl_p95_ms"],
        "setup_s": setup_s, "device": {"kind": kind, "count": len(devs)},
        "busy_s": red["busy_s"], "window_s": red["window_s"],
        "program_s": red["program_s"], "scope_s": red["scope_s"],
        "idle_by_span": red["idle_by_span"],
        "idle_gaps": red["idle_gaps"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
