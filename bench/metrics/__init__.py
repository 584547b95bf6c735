"""Per-layer metric readers, one file per metric, found by name.

``bench/metrics/<metric name>.py`` defines ``read(ctx)``, which returns
the metric's value or ``None`` where there is nothing to read (and the
harness then leaves the metric out).  ``ctx`` holds:

- ``trace``: ``bench.trace.reduce`` of the traced window, or ``None``;
- ``work``: what the cell's driver counted of the window's work, from
  ``bench.work``: for a served cell, per program (``prefill``,
  ``decode``), the operations (``flops``) and the least seconds
  (``min_s``) its requests needed; for training (``train``), the
  steps' operations and the ``chips`` they ran on;
- ``peak_flops``: the chip's bfloat16 peak, from ``bench.peaks``.

The helpers below are the arithmetic the readers share.
"""
from __future__ import annotations

import importlib.util
import pathlib
from typing import Callable, Optional

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str) -> Callable:
    path = HERE / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _program_s(ctx, program: str) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr:
        return None
    s = tr["program_s"].get(program, 0.0)
    return s if s > 0 else None


def busy_share(ctx, program: str) -> Optional[float]:
    """% of device busy time spent in ``program``."""
    s = _program_s(ctx, program)
    if s is None or not ctx["trace"]["busy_s"]:
        return None
    return 100.0 * s / ctx["trace"]["busy_s"]


def roofline(ctx, program: str) -> Optional[float]:
    """% of the program's device time that its work needs at least."""
    s = _program_s(ctx, program)
    w = ctx.get("work", {}).get(program)
    if s is None or not w or not w["min_s"]:
        return None
    return 100.0 * w["min_s"] / s


def mfu(ctx, program: str) -> Optional[float]:
    """Operations the program's work needs over its device time at the
    chip's peak rate, in %."""
    s = _program_s(ctx, program)
    w = ctx.get("work", {}).get(program)
    if s is None or not w or not w["flops"]:
        return None
    return 100.0 * w["flops"] / (s * ctx["peak_flops"])


def idle(ctx) -> Optional[float]:
    """% of the traced window in which no operation ran on the device."""
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
