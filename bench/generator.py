"""The one traffic generator: turns a traffic file into requests.

A traffic file (``bench/traffic/<mix>.json``) holds parameters only:
the arrival rate (``rate_per_s``), the number of requests in a block
(``block``), and the distributions of prompt and output lengths.

Every seed gets the same work in another order.  A block's prompt
lengths, output lengths and arrival gaps are stratified quantiles of
their distributions, one per request (the gaps exponential, with mean
``1 / rate_per_s``, as in a Poisson stream); the seed draws the order
of the lengths, the order of the gaps, and the token ids.  Block after
block follows, each in its own order, so the stream never ends and
every block spans ``block / rate_per_s`` seconds.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Iterator, List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int
    due: float                  # seconds after the window opens


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 0.5) / n of a length
    distribution, as whole numbers within [min, max]."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif dist["dist"] == "fixed":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gaps(traffic: dict) -> np.ndarray:
    """A block's arrival gaps: stratified quantiles of the exponential
    distribution, scaled so that they sum to ``block / rate_per_s``."""
    n = int(traffic["block"])
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (n / traffic["rate_per_s"] / g.sum())


def requests(traffic: dict, seed: int, vocab: int) -> Iterator[Request]:
    """Endless stream of requests in order of index and due time; the
    first is due when the window opens."""
    rng = np.random.default_rng(seed)
    n = int(traffic["block"])
    prompts = quantiles(traffic["prompt_len"], n)
    outputs = quantiles(traffic["output_len"], n)
    g0 = gaps(traffic)
    index, t0 = 0, 0.0
    while True:
        order = rng.permutation(n)
        g = rng.permutation(g0)
        due = t0 + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        for j, i in enumerate(order):
            tokens = rng.integers(0, vocab, int(prompts[i]), dtype=np.int32)
            yield Request(index, tokens, int(outputs[i]), float(due[j]))
            index += 1
        t0 += float(g.sum())


def take(traffic: dict, seed: int, vocab: int, count: int) -> List[Request]:
    it = requests(traffic, seed, vocab)
    return [next(it) for _ in range(count)]


def max_context(traffic: dict) -> int:
    """The longest prompt plus output the mix can draw."""
    return int(traffic["prompt_len"]["max"] + traffic["output_len"]["max"])
