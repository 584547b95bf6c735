"""One run of one cell: set-up, the measured window, the metrics and the
check, as ``bench/run.py`` prints them.

Everything that belongs to a cell is found by name: the workload entry
in ``BENCHMARK.json`` names its configuration (``bench/configs/``) and
traffic or job (``bench/traffic/``), whose ``driver`` names the module
that builds and drives it (``bench/serve.py``, ``bench/train.py``); its
limits are ``bench/limits/<cell>.json`` and each per-layer metric is
``bench/metrics/<metric>.py``.

A driver module defines ``Job(cfg, traffic, seed, seconds, devices,
log)``, whose construction is the set-up (weights, program, warm-up),
with ``run(annotate)`` (the window), ``end_to_end()`` (the end-to-end
metrics, with ``_attempted`` and ``_failed``), ``work(device_kind)``
(what the per-layer readers need besides the trace), ``release()``
(frees the program's state) and ``check(limits, control)`` (the
numbers that decide ``correct``, each beside its limit).
"""
from __future__ import annotations

import gc
import importlib
import json
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

from bench import metrics, model, peaks, trace
from bench.model import BENCH

ROOT = BENCH.parent


class NoChip(RuntimeError):
    pass


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def reported(bench: dict, cell: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metrics this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": layer}


def require_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def compile_cache():
    import jax
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def load_limits(cell: str) -> dict:
    with open(BENCH / "limits" / f"{cell}.json") as f:
        return json.load(f)


def driver(traffic: dict):
    """The module that drives a cell: ``bench/<traffic["driver"]>.py``."""
    return importlib.import_module(f"bench.{traffic['driver']}")


def passed(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def run_cell(cell: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, require_chip: bool = True,
             bench: Optional[dict] = None, cfg: Optional[dict] = None,
             traffic: Optional[dict] = None, limits: Optional[dict] = None,
             control: bool = False, log: Callable = None) -> dict:
    """Run one cell and return the result line's object.  Tests pass
    ``require_chip=False`` (which also leaves the persistent compile
    cache off) and small ``cfg``/``traffic``/``limits``; ``control``
    also reads the control's numbers against the same limits
    (``bench/control.py``)."""
    import jax

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    bench = bench or load_benchmark()
    w = workload(bench, cell)
    if require_chip:
        devs = require_devices(w["chips"])[:w["chips"]]
        cache_dir = compile_cache()
    else:
        devs, cache_dir = jax.devices()[:w["chips"]], None
    cfg = cfg or model.load_config(w["config"])
    traffic = traffic or load_traffic(w["traffic"])
    limits = limits or load_limits(cell)
    log(f"setup: import {time.perf_counter() - t_start:.3f} s, compile "
        f"cache {cache_dir}")

    job = driver(traffic).Job(cfg, traffic, seed, seconds, devs, log)
    setup_s = time.perf_counter() - t_start

    tmp = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    annotate = None
    if traced:
        # no Python tracer: it records every call and starves the loop
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        annotate = jax.profiler.TraceAnnotation
        jax.profiler.start_trace(tmp, profiler_options=opts)
    job.run(annotate=annotate)
    if traced:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    e2e = job.end_to_end()
    kind = devs[0].device_kind
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    e2e["setup_s"] = setup_s
    rep = reported(bench, cell)
    result: dict = {}
    if traced:
        red = trace.reduce(trace.load(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        ctx = {"trace": red, "peak_flops": peaks.peaks(kind)["bf16_flops"],
               "work": job.work(kind)}
        vals = {}
        for m in rep["per_layer"]:
            v = metrics.load(m["name"])(ctx)
            if v is not None:
                vals[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        log(f"trace: busy {red['busy_s']} s of {red['window_s']} s, "
            f"program seconds {red['program_s']}, collective "
            f"{red['collective_s']} s (exposed "
            f"{red['collective_exposed_s']} s), work {ctx['work']}")
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        vals = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in rep["end_to_end"]}
    job.release()
    gc.collect()

    res = job.check(limits, control=control)
    checks = res["checks"]
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    out = {"correct": passed(checks), "attempted": e2e["_attempted"],
           "failed": e2e["_failed"], "metrics": vals, "device": device}
    out.update(result)
    if control:
        out["control"] = dict(res["control"],
                              correct=passed(res["control"]["checks"]))
    out["checks"] = checks
    return out
