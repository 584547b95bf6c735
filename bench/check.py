"""Whether the served tokens are right: the comparison with the plain
reference that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and always holding the
one with the most served tokens, is run through the reference over
each prompt and its served tokens.  At each served position the number
read is how far the served token's logit lies below the reference's
best logit there (0 where it is the argmax); the run is correct when
the widest of those gaps stays within the cell's limit
(``bench/limits/<cell>.json``).  Greedy decoding only: the traffic
sends greedy requests.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.reference import forward


def sample(tracked, seed: int, min_tokens: int) -> List:
    """Finished requests: the one with the most served tokens, then
    others in an order drawn from the seed, until ``min_tokens`` served
    tokens are in."""
    done = [t for t in tracked
            if t.done and len(t.tokens) == t.req.max_new_tokens]
    if not done:
        return []
    done.sort(key=lambda t: (-len(t.tokens), t.req.index))
    rest = done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [done[0]], len(done[0].tokens)
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def rows_of(picked):
    rows, wanted, served = [], [], []
    for t in picked:
        S = len(t.req.prompt)
        toks = np.asarray(t.tokens, np.int32)
        rows.append(np.concatenate([t.req.prompt, toks[:-1]]))
        wanted.append(np.arange(S - 1, S - 1 + len(toks)))
        served.append(toks)
    return rows, wanted, served


def gaps(cfg: dict, seed: int, picked, control: bool = False) -> dict:
    """The widest gap of the served tokens, and with ``control`` the
    widest gap of the tokens the fp8 control puts first."""
    rows, wanted, served = rows_of(picked)
    ref = forward.logits_at(cfg, seed, rows, wanted)
    g = np.concatenate([forward.greedy_gaps(r, s)
                        for r, s in zip(ref, served)])
    out = {"max_gap": float(g.max()), "tokens": int(g.size),
           "requests": len(picked),
           "argmax_share": float(np.mean(g == 0))}
    if control:
        ctl = forward.logits_at(cfg, seed, rows, wanted,
                                transform=forward.fp8_weights)
        c = np.concatenate([forward.greedy_gaps(r, s, o)
                            for r, s, o in zip(ref, served, ctl)])
        out["control_max_gap"] = float(c.max())
        out["control_argmax_share"] = float(np.mean(c == 0))
    return out
