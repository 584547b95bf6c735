"""The closed-loop batch driver: a cell whose traffic file says
``"driver": "batch"``.

Offline batch generation: ``clients`` callers each keep one request in
flight and send the next as soon as the last one finishes, so with
more clients than slots every slot stays live and the scheduler's queue
never empties.  The program under test is the serving driver's
(``FrontDoor(make_engine(...))``, seeded weights), driven from one
thread the same way: pump the front door, read the tokens that reached
each handle, and for each request that finished, submit the next.

A request is due when it is submitted, so its time to first token
includes its wait in the queue; ``itl_p95_ms`` is the serving driver's
(``serve.end_to_end``).  Requests keep being sent after the window, so
load stays the same, until every request sent in the window has its
first token and finished requests hold the check's ``min_tokens``
served tokens, or ``drain_s`` has passed.

Lengths come in blocks of ``block`` requests, each block the stratified
quantiles of the length distributions in an order drawn from the seed
(as ``bench/generator.py`` draws them), so every seed gets the same
work in another order.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Iterator, List

import numpy as np

from bench import generator, model, serve


def requests(traffic: dict, seed: int, vocab: int
             ) -> Iterator[generator.Request]:
    """Endless stream of requests in submission order; each ``due`` is
    set when it is submitted."""
    rng = np.random.default_rng(seed)
    n = int(traffic["block"])
    prompts = generator.quantiles(traffic["prompt_len"], n)
    outputs = generator.quantiles(traffic["output_len"], n)
    index = 0
    while True:
        for i in rng.permutation(n):
            tokens = rng.integers(0, vocab, int(prompts[i]), dtype=np.int32)
            yield generator.Request(index, tokens, int(outputs[i]), 0.0)
            index += 1


def program_config(cfg: dict):
    """``model.program_config`` with the configuration's own gate
    normalisation (``norm_topk_prob``; DeepSeekMoE's is false), which
    ``model.program_config`` leaves at the program's default."""
    pcfg = model.program_config(cfg)
    if pcfg.moe is None:
        return pcfg
    return dataclasses.replace(pcfg, moe=dataclasses.replace(
        pcfg.moe, norm_topk_prob=bool(cfg["norm_topk_prob"])))


class Job(serve.Job):
    """One closed-loop batch cell: the serving driver's weights, engine,
    warm-up, work counts and check, with its own window."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 devices, log):
        import jax
        from repro.serve import FrontDoor, make_engine
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds = seed, seconds
        self.pcfg = program_config(cfg)
        t0 = time.perf_counter()
        params = model.program_weights(cfg, self.pcfg, seed)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        server = traffic["server"]
        page = server["page_size"]
        need = generator.max_context(traffic) + server["decode_chunk"]
        self.max_len = -(-need // page) * page
        self.num_pages = serve.page_budget(cfg, server, self.max_len)
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
        self.window = None

    def run(self, annotate=None) -> None:
        """The measured window.  ``annotate(name)`` gives a context
        manager for a host span (the profiler's, in a traced run); the
        ``window`` span closes when the window does, before the drain."""
        span = annotate or (lambda name: contextlib.nullcontext())
        tr = self.traffic
        T = float(self.seconds)
        drain_s = float(tr["check"]["drain_s"])
        min_tokens = int(tr["check"]["min_tokens"])
        stream = requests(tr, self.seed, self.pcfg.vocab_size)
        tracked: List[serve.Tracked] = []
        live: List[serve.Tracked] = []
        pumps: List[serve.Pump] = []
        in_flight: List[int] = []           # requests in flight, per pump
        finished_tokens = 0
        clock = time.perf_counter
        t0 = clock()

        def send(now):
            req = next(stream)
            req.due = now
            with span("submit"):
                h = self.fd.submit(req.prompt, req.max_new_tokens)
            tk = serve.Tracked(req, h, now)
            tracked.append(tk)
            live.append(tk)

        window = contextlib.ExitStack()
        window.enter_context(span("window"))
        with window:
            for _ in range(int(tr["clients"])):
                send(0.0)
            window_open = True
            while True:
                now = clock() - t0
                if now >= T:
                    if window_open:
                        window_open = False
                        window.close()
                    waiting = any(t.req.due < T and t.first is None
                                  for t in tracked)
                    if (not waiting and finished_tokens >= min_tokens) \
                            or now >= T + drain_s:
                        break
                in_flight.append(len(live))
                start = now
                with span("pump"):
                    self.fd.pump()
                end = clock() - t0
                pump = serve.Pump(start, [], [])
                done = 0
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
                            done += 1
                    for _ in range(done):
                        send(end)
                pumps.append(pump)
        for tk in tracked:              # handles hold the engine and pool
            tk.handle = None
        stats = self.engine.stats()
        self.window = {"T": T, "tracked": tracked, "stop": clock() - t0,
                       "pumps": [p for p in pumps if p.start < T],
                       "in_flight": in_flight}
        e = self.end_to_end()
        in_window = self.window["pumps"]
        with_prefill = sum(1 for p in in_window if p.prefills)
        finished = sum(1 for t in tracked if t.done and t.times[-1] < T)
        self.log(
            f"window: {T} s, {e['_attempted']} requests sent, {finished} "
            f"finished, {e['_tokens']} tokens, {e['_gaps']} token gaps, "
            f"{e['_failed']} without a first token, {min(in_flight)} to "
            f"{max(in_flight)} in flight; {len(in_window)} pumps, {with_prefill} with a "
            f"prefill ({100.0 * with_prefill / max(1, len(in_window)):.2f}"
            f"%); drain ended {self.window['stop'] - T:.3f} s after the "
            f"window with {finished_tokens} tokens of finished requests; "
            f"engine: dispatches {stats['dispatches']}, host syncs "
            f"{stats['host_syncs']}, prefill dispatches "
            f"{stats['prefill_dispatches']}, decode dispatches "
            f"{stats['decode_dispatches']}, decode occupancy "
            f"{stats['decode_occupancy']:.4f}")
