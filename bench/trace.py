"""From a profiler trace to device busy time, program time and gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into a small
event table; ``reduce`` computes everything the per-layer metrics read
from it.  The table is plain JSON-able data, so a trimmed one recorded
on the chip serves as the test's fixture:

    {"window": [start_ns, end_ns],
     "devices": {plane: {"modules": [[name, start_ns, dur_ns], ...],
                         "ops": [[name, start_ns, dur_ns], ...]}},
     "host": [[span name, start_ns, dur_ns], ...]}

``modules`` are whole compiled programs (named after the jitted
function), ``ops`` the operations inside them.  ``host`` keeps only the
benchmark's own spans (``SPANS``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPANS = ("window", "submit", "pump", "deliver", "wait")
# programs the per-layer metrics split device time by, matched in the
# module's name
PROGRAMS = {"prefill": ("prefill_chunk_fn", "prefill_last_fn"),
            "decode": ("decode_loop_fn",)}
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute",
    re.I)


def load(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                # an op's name is its HLO instruction, signature and all:
                # keep what precedes " = "
                dev[key] = [[e.name.split(" = ", 1)[0], int(e.start_ns),
                             int(e.duration_ns)] for e in line.events]
            if dev["modules"] or dev["ops"]:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    wins = [h for h in host if h[0] == "window"]
    window = ([wins[0][1], wins[0][1] + wins[0][2]] if wins else None)
    return {"window": window, "devices": devices, "host": host}


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def _clip(events, lo, hi) -> List[Tuple[int, int]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def _subtract(a_ivs, b_ivs) -> List[Tuple[int, int]]:
    """Parts of the (unioned) intervals ``a_ivs`` not covered by the
    (unioned) ``b_ivs``."""
    out, j = [], 0
    for a, b in a_ivs:
        cur = a
        while j < len(b_ivs) and b_ivs[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_ivs) and b_ivs[k][0] < b:
            if b_ivs[k][0] > cur:
                out.append((cur, b_ivs[k][0]))
            cur = max(cur, b_ivs[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _label(host, mid) -> str:
    """The innermost benchmark span (shortest) that covers ``mid``."""
    best: Optional[list] = None
    for name, s, d in host:
        if name != "window" and s <= mid < s + d:
            if best is None or d < best[2]:
                best = [name, s, d]
    return best[0] if best else "outside spans"


def program_of(name: str) -> Optional[str]:
    for prog, keys in PROGRAMS.items():
        if any(k in name for k in keys):
            return prog
    return None


def reduce(tr: dict) -> dict:
    """Busy and idle time per device over the window, device time per
    program, collective time (all of it, and the part with no other
    operation running on that device), the longest idle gaps labelled
    by the host span they fall in, and the operations that took most
    time."""
    lo, hi = tr["window"]
    window_s = (hi - lo) * 1e-9
    busy, program, coll, exposed = [], defaultdict(float), [], []
    op_time: Dict[str, float] = defaultdict(float)
    gaps = []
    for name, dev in tr["devices"].items():
        ops = dev["ops"] or dev["modules"]
        b = union(_clip(ops, lo, hi))
        busy.append(_length(b) * 1e-9)
        for mname, s, d in dev["modules"]:
            prog = program_of(mname)
            if prog:
                program[prog] += _length(_clip([[mname, s, d]], lo, hi)) * 1e-9
        c = union(_clip([e for e in dev["ops"] if _COLLECTIVE.search(e[0])],
                        lo, hi))
        other = union(_clip([e for e in dev["ops"]
                             if not _COLLECTIVE.search(e[0])], lo, hi))
        coll.append(_length(c) * 1e-9)
        exposed.append(_length(_subtract(c, other)) * 1e-9)
        for oname, s, d in dev["ops"]:
            a, z = max(s, lo), min(s + d, hi)
            if z > a:
                op_time[oname] += (z - a) * 1e-9
        gaps.extend(_subtract([(lo, hi)], b))
    n = max(1, len(tr["devices"]))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    gaps = [(_label(tr["host"], (a + z) // 2), (z - a) * 1e-9)
            for a, z in gaps]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "program_s": {k: v / n for k, v in program.items()},
        "collective_s": sum(coll) / n,
        "collective_exposed_s": sum(exposed) / n,
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[name, s] for name, s in gaps],
        "devices": n,
    }
