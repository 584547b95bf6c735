"""The serving driver: a cell whose traffic file says ``"driver":
"serve"``.

The program under test is ``FrontDoor(make_engine(...))`` with the
configuration's seeded weights.  The window is driven from one thread:
submit every request that is due, pump the front door once, read the
tokens that reached each handle, repeat.  Each token's time is taken
when the pump that delivered it returns, which is when a client would
see it.

Open loop: requests fall due on the traffic's schedule, whether or not
the engine keeps up, and a request's time to first token runs from
when it was due.  After the window the schedule goes on, so load stays
the same, until every request due in the window has its first token
and finished requests hold the check's ``min_tokens`` served tokens,
or ``drain_s`` has passed; a request due in the window that still has
no first token then has failed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import check, generator, model, peaks, work


@dataclasses.dataclass
class Tracked:
    req: generator.Request
    handle: object                      # dropped when the window ends
    submitted: float
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None


@dataclasses.dataclass
class Pump:
    """What one pump did, as the harness sees it from its requests."""
    start: float
    prefills: List[int]                 # prompt lengths prefilled
    decode: List[tuple]                 # (context before, tokens) per request


def page_budget(cfg: dict, server: dict, max_len: int) -> int:
    """Pages of KV pool: ``kv_pool_gb`` of the traffic's server settings,
    and no more than every slot at its longest needs.  (The program's
    decode and prefill steps hold a second copy of the pool while they
    run, so a chip holds weights + 2 x pool + the steps' other
    temporaries.)"""
    page = server["page_size"]
    want = server["slots"] * (max_len // page) + 1
    have = int(server["kv_pool_gb"] * 1e9
               // (work.kv_bytes_per_token(cfg) * page))
    return max(2, min(want, have))


def tails(lengths, chunk: int) -> List[int]:
    """The distinct lengths of the last prefill chunk."""
    return sorted({(int(n) - 1) % chunk + 1 for n in lengths})


class Job:
    """One served cell: weights, engine, front door, traffic."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 devices, log):
        import jax
        from repro.serve import FrontDoor, make_engine
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds = seed, seconds
        self.pcfg = model.program_config(cfg)
        t0 = time.perf_counter()
        params = model.program_weights(cfg, self.pcfg, seed)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        server = traffic["server"]
        page = server["page_size"]
        need = generator.max_context(traffic) + server["decode_chunk"]
        self.max_len = -(-need // page) * page
        self.num_pages = page_budget(cfg, server, self.max_len)
        self.engine = make_engine(
            self.pcfg, params, batch_size=server["slots"],
            max_len=self.max_len, dtype=self.pcfg.dtype, page_size=page,
            num_pages=self.num_pages,
            prefill_chunk=server["prefill_chunk"],
            decode_chunk=server["decode_chunk"], seed=seed)
        self.fd = FrontDoor(self.engine)
        del params
        self.log = log
        n_warm = self.warm_up()
        log(f"setup: weights {t1 - t0:.3f} s, engine and warm-up ({n_warm} "
            f"requests) {time.perf_counter() - t1:.3f} s; KV pool "
            f"{self.num_pages} pages of {page}, max_len {self.max_len}")
        self.window: Optional[dict] = None

    # ------------------------------------------------------------------
    def warm_up(self) -> int:
        """Compile every program this traffic will run: the full prefill
        chunk, the last chunk at each tail length the mix draws, and the
        decode loop.  Returns the number of warm-up requests."""
        chunk = self.traffic["server"]["prefill_chunk"]
        lengths = generator.quantiles(self.traffic["prompt_len"],
                                      int(self.traffic["block"]))
        rng = np.random.default_rng(self.seed)
        hs = []
        for t in tails(lengths, chunk):
            prompt = rng.integers(0, self.pcfg.vocab_size, chunk + t,
                                  dtype=np.int32)
            hs.append(self.fd.submit(prompt, 2))
        self.fd.drain()
        for h in hs:
            h.result()
        return len(hs)

    # ------------------------------------------------------------------
    def run(self, annotate=None) -> None:
        """The measured window.  ``annotate(name)`` gives a context
        manager for a host span (the profiler's, in a traced run); the
        ``window`` span closes when the window does, before the drain."""
        span = annotate or (lambda name: contextlib.nullcontext())
        tr = self.traffic
        T = float(self.seconds)
        drain_s = float(tr["check"]["drain_s"])
        min_tokens = int(tr["check"]["min_tokens"])
        stream = generator.requests(tr, self.seed, self.pcfg.vocab_size)
        tracked: List[Tracked] = []
        live: List[Tracked] = []
        pumps: List[Pump] = []
        lateness: List[float] = []
        finished_tokens = 0
        nxt = next(stream)
        window_open = True
        clock = time.perf_counter
        t0 = clock()

        window = contextlib.ExitStack()
        window.enter_context(span("window"))
        with window:
            while True:
                now = clock() - t0
                while nxt.due <= now:
                    with span("submit"):
                        h = self.fd.submit(nxt.prompt, nxt.max_new_tokens)
                    tk = Tracked(nxt, h, now)
                    tracked.append(tk)
                    live.append(tk)
                    lateness.append(now - nxt.due)
                    nxt = next(stream)
                if now >= T:
                    if window_open:
                        window_open = False
                        window.close()
                    waiting = any(t.req.due < T and t.first is None
                                  for t in tracked)
                    if (not waiting and finished_tokens >= min_tokens) \
                            or now >= T + drain_s:
                        break
                if not live:
                    with span("wait"):
                        until = min(nxt.due, T) if window_open else nxt.due
                        time.sleep(max(0.0, until - now))
                    continue
                start = now
                with span("pump"):
                    self.fd.pump()
                end = clock() - t0
                pump = Pump(start, [], [])
                with span("deliver"):
                    for tk in list(live):
                        new = tk.handle.available()
                        if new:
                            before = len(tk.tokens)
                            n_dec = len(new) - (1 if before == 0 else 0)
                            if before == 0:
                                pump.prefills.append(len(tk.req.prompt))
                            if n_dec:
                                ctx = len(tk.req.prompt) + max(before, 1)
                                pump.decode.append((ctx, n_dec))
                            tk.tokens.extend(int(t) for t in new)
                            tk.times.extend([end] * len(new))
                        if tk.handle.done:
                            tk.done = True
                            live.remove(tk)
                            finished_tokens += len(tk.tokens)
                pumps.append(pump)
        for tk in tracked:              # handles hold the engine and pool
            tk.handle = None
        stats = self.engine.stats()
        self.window = {"T": T, "tracked": tracked, "stop": clock() - t0,
                       "pumps": [p for p in pumps if p.start < T]}
        e = end_to_end(self.window)
        lat = sorted(lateness)
        self.log(
            f"window: {T} s, {e['_attempted']} requests due, "
            f"{len(tracked)} submitted, {e['_tokens']} tokens, "
            f"{e['_gaps']} token gaps, {e['_failed']} without a first "
            f"token; drain ended {self.window['stop'] - T:.3f} s after the "
            f"window with {finished_tokens} tokens of finished requests; "
            f"generator lateness p50 {percentile(lat, 50):.6f} s, max "
            f"{lat[-1]:.6f} s; engine: dispatches {stats['dispatches']}, "
            f"host syncs {stats['host_syncs']}, prefill dispatches "
            f"{stats['prefill_dispatches']}, decode dispatches "
            f"{stats['decode_dispatches']}; ttft_s sorted "
            f"{sorted(round(x, 3) for x in e['_ttft'])}")

    def release(self):
        self.fd = None
        self.engine = None

    # ------------------------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        return end_to_end(self.window)

    def work(self, device_kind: str) -> dict:
        """Operations and least seconds of the prefill and decode work
        the window's pumps did, as the harness knows it from its
        requests."""
        out = {p: {"flops": 0.0, "bytes": 0.0, "min_s": 0.0}
               for p in ("prefill", "decode")}
        cfg = self.cfg
        chunk = self.traffic["server"]["prefill_chunk"]

        def add(prog, f, b):
            out[prog]["flops"] += f
            out[prog]["bytes"] += b
            out[prog]["min_s"] += peaks.min_seconds(f, b, device_kind)

        for p in self.window["pumps"]:
            for S in p.prefills:
                for start, length, last in work.prefill_chunks(S, chunk):
                    add("prefill",
                        *work.prefill_chunk(cfg, start, length, last))
            steps = max((n for _, n in p.decode), default=0)
            for s in range(steps):
                keys = [ctx + s for ctx, n in p.decode if n > s]
                add("decode", *work.decode_step(cfg, keys))
        return out

    def check(self, limits: dict, control: bool = False) -> dict:
        """The numbers that decide ``correct``, each with its limit."""
        picked = check.sample(self.window["tracked"], self.seed,
                              self.traffic["check"]["min_tokens"])
        t0 = time.perf_counter()
        if picked:
            g = check.gaps(self.cfg, self.seed, picked, control=control)
        else:
            g = {"max_gap": None, "tokens": 0, "requests": 0,
                 "argmax_share": 0.0}
        self.log(f"check: {g['requests']} finished requests, {g['tokens']} "
                 f"served tokens, argmax share {g['argmax_share']}, "
                 f"reference {time.perf_counter() - t0:.3f} s")
        out = {"checks": {"max_gap": {"value": g["max_gap"],
                                      "limit": limits["max_gap"]["limit"]}}}
        if control:
            out["control"] = {
                "checks": {"max_gap": {
                    "value": g.get("control_max_gap"),
                    "limit": limits["max_gap"]["limit"]}},
                "argmax_share": g.get("control_argmax_share")}
        return out


# --------------------------------------------------------------------------
# end-to-end metrics from the window
# --------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by the nearest rank, so it is always
    one of the values."""
    v = sorted(values)
    if not v:
        return float("inf")
    rank = max(1, int(np.ceil(q / 100.0 * len(v))))
    return float(v[rank - 1])


def end_to_end(window: dict) -> Dict[str, float]:
    """A request due in the window with no first token when the run
    stopped waiting counts with its whole wait, from due to the stop
    (at least ``drain_s``): longer than any served one, and finite so
    the result stays JSON."""
    T = window["T"]
    tracked = window["tracked"]
    due = [t for t in tracked if t.req.due < T]
    ttft = [(t.first if t.first is not None else window["stop"]) - t.req.due
            for t in due]
    gaps = []
    for t in tracked:
        for a, b in zip(t.times, t.times[1:]):
            if b < T:
                gaps.append(b - a)
    failed = sum(1 for t in due if t.first is None)
    return {"ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "itl_p95_ms": 1e3 * percentile(gaps, 95),
            "_attempted": len(due),
            "_failed": failed,
            "_ttft": ttft,
            "_gaps": len(gaps),
            "_tokens": sum(1 for t in tracked for s in t.times if s < T)}
