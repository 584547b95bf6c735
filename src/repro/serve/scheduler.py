"""Continuous-batching scheduler over the paged KV cache.

Replaces ``ServeEngine``'s lockstep ``generate`` for production-shaped
serving: a request queue, slot admission the moment a slot retires,
chunked prefill interleaved with decode, and a FUSED device-side decode
loop (``jax.lax.scan`` over sample→decode with on-device EOS masking)
that costs ONE dispatch + ONE host sync per ``decode_chunk`` tokens —
the legacy engine pays a blocking host round-trip per token.

Request lifecycle::

    QUEUED     submit() enqueued it (priority-ordered; FIFO within a
               priority); waiting for a slot + pages + tenant quota
    PREFILL    admitted: cached prefix pages ALIASED into the page
               table (``prefix_cache=True`` — refcount +1 each, zero
               bytes moved), remaining pages allocated, SSM state
               zeroed, the UNMATCHED prompt suffix fed in
               `prefill_chunk`-token chunks (B=1 calls that scatter
               into the shared pool); the LAST chunk's compiled call
               also samples the first token — chunk + sample is one
               dispatch and one host sync, no separate sampling launch
    DECODE     slot participates in the fused batched decode loop
    RETIRED    EOS emitted (device-detected) or token budget reached
               (host-detected): the slot's page references dropped
               (shared pages survive under the radix tree's reference),
               table row -> trash, the next queued request admits into
               the slot

Admission replaces pure FIFO with priority order (higher ``priority``
first, submit order within a class) and per-tenant quotas
(``tenant_quota``: at most N concurrently-active slots per tenant —
quota-blocked requests are SKIPPED, not head-of-line blockers).  The
``tick()`` quantum (one admission pass + one fused decode tick) is the
streaming front door's pump: ``serve.frontdoor.FrontDoor`` wraps
``submit``/``tick``/``take_results`` into non-blocking submission with
per-request token streams.

Greedy outputs are bitwise-identical to the legacy slab engine per
request (same einsum shapes, same masking value; extra gather width
only ever adds exactly-zero softmax terms), which
``tests/test_serve.py`` pins both lockstep and staggered.

Speculative decode (``spec_decode=k``, greedy-only, needs
``cfg.mtp_depth > 0``): the fused loop body becomes draft→verify→accept
— the MTP head drafts ``k-1`` tokens from the last accepted hidden
state, ONE verify forward scores the k-token chunk through the paged
pool (the kernels' multi-token per-query-causal path), the longest
matching prefix is accepted on device, and rejected positions roll
back by rewinding per-slot ``cache_pos`` into already-allocated page
slack.  Dispatch discipline is unchanged — still one dispatch + one
host sync per ``decode_chunk`` scan steps — but each step now emits
1..k tokens, and greedy outputs stay bitwise-equal to the
non-speculative engine because every emitted token IS the verify
argmax.  See ``docs/serving.md`` § Speculative decode.

Mesh serving: pass ``mesh=`` (a ``(data, model)`` serve mesh — the
production topology) and the engine becomes mesh-native: params are
placed with the serve-mode parameter shardings, the paged pool is
allocated model-sharded (``sharding.rules.pool_spec``), every compiled
call runs under the scoped serve topology (``sharding.ctx.
serve_topology``) so activation constraints and the expert-parallel
MoE ``shard_map`` dispatch engage, and the pool's sharding is pinned
through prefill and the fused loop with explicit constraints.  The
host-sync discipline is UNCHANGED — still one blocking sync per decode
chunk; scheduling stays host-side bookkeeping either way.

Not supported here (use ``ServeEngine``/``apply_model`` directly):
encoder-decoder and vision-frontend architectures.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import apply_model, mtp_draft
from repro.models.attention import PagedView
from repro.serve.kvcache import PagedKVCache
from repro.serve.prefix import PrefixCache
from repro.serve.sampling import (
    SamplingConfig, accept_speculative, masked_sample, sample)
from repro.sharding import ctx as shctx

__all__ = ["ServeRequest", "ContinuousScheduler", "SPANS", "SCOPES"]

# Host spans the scheduler writes into a running profiler's trace, on
# the clock of its device events (``jax.profiler.TraceAnnotation``: a
# no-op when no profiler runs).  Nested as listed: a tick holds the
# admission pass (which holds each admitted request's prefill and its
# first-token sync) and the decode tick (dispatch, sync, emit).  Every
# argument is a host value; no span reads the device.
SPANS = ("sched.tick", "sched.admit", "sched.prefill", "sched.prefill.sync",
         "sched.decode", "sched.decode.dispatch", "sched.decode.sync",
         "sched.decode.emit")
# ``jax.named_scope`` names in the serving programs' ``op_name``
# metadata (models/ and the sampling below), so a device trace splits
# each program's time by the part of the model step it ran
SCOPES = ("embed", "layers", "layer", "attn.qkv", "attn.kv_write",
          "attn.kv_read", "attn.core", "attn.out", "mlp", "moe", "moe.route",
          "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
          "lm_head", "sample")
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class ServeRequest:
    uid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int
    priority: int = 0                  # higher admits first
    tenant: Optional[str] = None       # per-tenant quota key
    prefix_tokens: int = 0             # prompt tokens served from cache
    spec_steps: int = 0                # verify steps this request rode
    spec_accepted: int = 0             # draft tokens accepted for it
    out: List[int] = dataclasses.field(default_factory=list)
    # ``time.perf_counter()`` stamps: monotonic, so the queue wait and
    # the time to first token never go negative
    t_submit: float = 0.0
    t_first: Optional[float] = None    # time-to-first-token timestamp
    t_done: Optional[float] = None

    @property
    def ttft(self) -> Optional[float]:
        return None if self.t_first is None else self.t_first - self.t_submit


class ContinuousScheduler:
    """Continuous batching over ``slots`` fixed batch lanes.

    cfg/params   — model config + host/device param pytree.
    slots        — decode batch width (lanes).
    max_len      — per-slot logical context bound (page-aligned).
    page_size    — tokens per KV page.
    num_pages    — pool size; default slots*max_len/page_size + trash,
                   i.e. no saving — size it DOWN to the live-token
                   budget to realise the paged-HBM win.
    eos_id       — on-device EOS detection; None = budget-only.
    pad_id       — what retired slots emit (default: eos_id or 0).
    prefill_chunk/decode_chunk — scheduling granularity: prompt tokens
                   per prefill call; decoded tokens per fused loop.
    mesh         — optional serve mesh; when set, params and the paged
                   pool are placed model-sharded and every compiled call
                   runs under the scoped serve topology.
    prefix_cache — radix-tree prefix reuse (``serve.prefix``): matched
                   prompt pages are aliased instead of re-prefilled.
                   Attention/MLA architectures only (recurrent SSM
                   state is not captured by KV pages).
    tenant_quota — max concurrently-active slots per tenant: an int
                   (every tenant) or ``{tenant: n}`` dict (unlisted
                   tenants are unquota'd).  Quotas must be >= 1.
    spec_decode  — 0 = off; k >= 2 = speculative decode with k-token
                   verify chunks (the carried token + k-1 MTP drafts
                   per scan step).  Greedy-only (temperature must be 0
                   — lossless acceptance needs argmax targets) and
                   requires ``cfg.mtp_depth > 0``.
    """

    def __init__(self, cfg, params, *, slots, max_len, dtype=jnp.float32,
                 eos_id: Optional[int] = None, pad_id: Optional[int] = None,
                 sampling: SamplingConfig = SamplingConfig(), seed: int = 0,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: int = 32, decode_chunk: int = 8,
                 mesh: object = None, prefix_cache: bool = False,
                 tenant_quota=None, spec_decode: int = 0):
        if cfg.is_encoder_decoder or cfg.frontend != "none":
            raise ValueError("continuous batching drives decoder-only "
                             "text architectures")
        if prefix_cache and any(mix != "attn"
                                for (mix, _f) in cfg.layer_pattern()):
            raise ValueError(
                "prefix_cache=True needs an attention/MLA-only stack: a "
                "recurrent (SSM) mixer's state at the suffix boundary is "
                "not captured by KV pages, so aliased prefixes would "
                "serve with a zeroed recurrent state")
        if tenant_quota is not None:
            vals = (tenant_quota.values()
                    if isinstance(tenant_quota, dict) else [tenant_quota])
            if any(int(v) < 1 for v in vals):
                raise ValueError("tenant_quota entries must be >= 1 (a "
                                 "0 quota deadlocks admission)")
        self.cfg = cfg
        self.mesh = mesh
        self._topo = (None if mesh is None
                      else shctx.ServeTopology.from_mesh(mesh))
        if mesh is not None:
            from repro.sharding.rules import ShardingConfig, param_shardings
            shapes = jax.eval_shape(lambda: params)
            params = jax.device_put(
                params,
                param_shardings(cfg, mesh, shapes,
                                ShardingConfig.for_mode("serve")))
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        if pad_id is None:
            pad_id = eos_id if eos_id is not None else 0
        self.pad_id = pad_id
        self.sampling = sampling
        self.prefill_chunk = prefill_chunk
        self.decode_chunk = decode_chunk
        self.spec_decode = int(spec_decode or 0)
        if self.spec_decode:
            if self.spec_decode < 2:
                raise ValueError(
                    "spec_decode counts the whole verify chunk (the "
                    "carried token + the drafts); k=1 is plain decode — "
                    "pass spec_decode >= 2 or 0")
            if cfg.mtp_depth <= 0:
                raise ValueError(
                    "spec_decode needs an architecture with MTP heads "
                    "(cfg.mtp_depth > 0) to draft from; this config has "
                    "none")
            if sampling.temperature > 0:
                raise ValueError(
                    "speculative decode is greedy-only: lossless "
                    "acceptance emits the verify argmax, which only "
                    "equals the engine's output at temperature=0")
        # decode-overshoot page slack per slot, beyond prompt+budget:
        # the fused loop may overrun the budget within a tick (host
        # truncation happens after the sync), and a rejected draft
        # additionally writes up to spec_decode-1 positions past the
        # last accepted one — all of it must land in allocated pages
        self._chunk_slack = (self.decode_chunk * self.spec_decode
                             + self.spec_decode
                             if self.spec_decode else self.decode_chunk)
        self.kv = PagedKVCache(cfg, slots=slots, max_len=max_len,
                               page_size=page_size, num_pages=num_pages,
                               dtype=dtype, mesh=mesh)
        self.prefix = PrefixCache(self.kv) if prefix_cache else None
        self.tenant_quota = tenant_quota
        self._key = jax.random.PRNGKey(seed)
        self._tok = jnp.zeros((slots, 1), jnp.int32)
        self._pos = jnp.zeros((slots,), jnp.int32)
        # trunk hidden at each slot's last accepted position — the MTP
        # draft head's input (speculative decode only; dead otherwise)
        self._hid = jnp.zeros((slots, cfg.d_model), jnp.dtype(cfg.dtype))
        self._done_host = np.ones((slots,), bool)      # idle == done
        self._done = jnp.asarray(self._done_host)
        self._pending: List[tuple] = []    # heap: (-priority, uid, req)
        self._active: Dict[int, ServeRequest] = {}
        self._results: Dict[int, ServeRequest] = {}
        self._byuid: Dict[int, ServeRequest] = {}      # submit -> handoff
        self._uid = 0
        # ---- telemetry ----
        self._ttft: List[float] = []   # window: reset at each run()
        self._ttft_n_cum = 0           # cumulative across the lifetime
        self._ttft_sum_cum = 0.0
        self.host_syncs = 0            # blocking device->host pulls
        self.dispatches = 0            # compiled-call launches
        # per-phase splits of the two aggregates above (the dispatch-
        # discipline microbenchmark and `launch.serve --report` read
        # these): prefill = chunk scatters + the fused first-token
        # sample; decode = fused loop ticks
        self.prefill_dispatches = 0
        self.prefill_host_syncs = 0
        self.decode_dispatches = 0
        self.decode_host_syncs = 0
        self.tokens_out = 0
        self.prefix_tokens_saved = 0   # prompt tokens served by aliasing
        self.prompt_tokens = 0
        # queue wait: submit -> start of the request's prefill, summed
        # over the `admitted` requests
        self.admitted = 0
        self.queue_wait_s_sum = 0.0
        # batch occupancy: lanes x scan steps of every decode tick, and
        # the part of them that carried a live request
        self.decode_lane_steps = 0
        self.decode_live_lane_steps = 0
        # speculative-decode telemetry: acceptance is accepted/offered
        # drafts over live verify steps; per-slot arrays give the
        # accepted-length profile of each lane
        self.spec_verify_steps = 0
        self.spec_draft_tokens = 0     # offered: (k-1) per live step
        self.spec_accepted_tokens = 0
        self._spec_slot_steps = np.zeros((slots,), np.int64)
        self._spec_slot_accepted = np.zeros((slots,), np.int64)
        self._build_steps()

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------
    def _build_steps(self):
        cfg, page_size = self.cfg, self.kv.page_size
        sc = self.sampling
        eos_id, pad_id = self.eos_id, self.pad_id
        K = self.decode_chunk
        shardings = self.kv.shardings

        def pin(cache):
            """Re-assert the pool's placement on a cache RESULT so GSPMD
            cannot drift it (pooled leaves model-sharded, per-slot
            leaves replicated — the specs are rank-stable, so they also
            fit prefill's B=1 slot_cache slices).  Host path: no-op."""
            if shardings is None:
                return cache
            return jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, cache, shardings)

        def prefill_chunk_fn(params, cache, table_row, tokens, pos):
            """B=1: scatter one prompt chunk into the pool; logits at
            the chunk's last position.  Chunks are EXACT (full chunks
            plus a ragged tail, one compile per distinct length) — a
            padded lane would be maskable for attention but would
            corrupt the per-slot recurrent SSM state, which integrates
            every token it sees."""
            view = PagedView(table_row, page_size)
            out = apply_model(cfg, params, {"tokens": tokens},
                              mode="decode", cache=cache, cache_pos=pos,
                              paged=view)
            return pin(out["cache"]), out["logits"][:, -1]

        def prefill_last_fn(params, cache, table_row, tokens, pos, key):
            """The FINAL prompt chunk with the first-token sample fused
            into the same compiled call: chunk scatter + logits +
            sample is one dispatch, and the returned token is the one
            host sync of the whole prefill — the decode loop's
            dispatch discipline, applied to prefill's epilogue."""
            view = PagedView(table_row, page_size)
            out = apply_model(cfg, params, {"tokens": tokens},
                              mode="decode", cache=cache, cache_pos=pos,
                              paged=view)
            with jax.named_scope("sample"):
                first = sample(out["logits"][:, -1], key,
                               sc=sc)[0].astype(jnp.int32)
            return pin(out["cache"]), first, out["hidden"][:, -1]

        def decode_loop_fn(params, cache, table, tok, pos, done, key):
            """The fused loop: K sample→decode steps on device.  Done
            (and idle) slots emit `pad_id`, freeze their position, and
            — because their table rows are zero — scatter into the
            trash page."""
            view = PagedView(table, page_size)

            def body(carry, _):
                cache, tok, pos, done, key = carry
                out = apply_model(cfg, params, {"tokens": tok},
                                  mode="decode", cache=cache,
                                  cache_pos=pos, paged=view)
                with jax.named_scope("sample"):
                    key, sub = jax.random.split(key)
                    nxt = masked_sample(out["logits"][:, -1], sub, done,
                                        pad_id, sc=sc)
                pos = pos + jnp.where(done, 0, 1)
                if eos_id is not None:
                    done = done | (nxt == eos_id)
                return (pin(out["cache"]), nxt[:, None], pos, done,
                        key), nxt

            carry, toks = jax.lax.scan(
                body, (cache, tok, pos, done, key), None, length=K)
            return carry + (toks.T,)          # (..., (slots, K))

        spec_k = self.spec_decode

        def spec_loop_fn(params, cache, table, tok, pos, hid, done):
            """Draft→verify→accept fused loop: K scan steps, each
            emitting 1..k tokens for one model dispatch.  Per step: the
            MTP head drafts k-1 tokens from `hid` (the trunk hidden at
            the last accepted position), ONE verify forward scores the
            k-token chunk [tok, drafts] at positions pos..pos+k-1
            through the paged pool (per-query-causal multi-token path),
            and the longest matching prefix of the greedy targets is
            accepted.  Rollback is a cache_pos REWIND: rejected
            positions' K/V stay written in the slot's allocated slack,
            masked out by `kv_positions <= q_positions`, and the next
            chunk (k wide, starting at pos+acc+1) overwrites every
            stale position before any query can reach it — no page
            frees, no extra host syncs."""
            view = PagedView(table, page_size)
            lanes = jnp.arange(tok.shape[0])

            def body(carry, _):
                cache, tok, pos, hid, done = carry
                drafts, _ = mtp_draft(cfg, params, hid[:, None, :], tok,
                                      spec_k - 1)
                chunk = jnp.concatenate([tok, drafts], axis=1)   # (B, k)
                out = apply_model(cfg, params, {"tokens": chunk},
                                  mode="decode", cache=cache,
                                  cache_pos=pos, paged=view)
                with jax.named_scope("sample"):
                    tgt = jnp.argmax(out["logits"],
                                     axis=-1).astype(jnp.int32)  # (B, k)
                    emit, n_emit, n_acc, done_new = accept_speculative(
                        tgt, chunk, done, pad_id, eos_id)
                pos = pos + jnp.where(done, 0, n_acc + 1)
                nxt = tgt[lanes, jnp.maximum(n_emit - 1, 0)]
                nxt = jnp.where(done_new, jnp.int32(pad_id), nxt)[:, None]
                hid = jnp.where(done_new[:, None], hid,
                                out["hidden"][lanes, n_acc])
                return (pin(out["cache"]), nxt, pos, hid,
                        done_new), (emit, n_emit)

            carry, (toks, counts) = jax.lax.scan(
                body, (cache, tok, pos, hid, done), None, length=K)
            # toks (K, B, k) -> (B, K, k); counts (K, B) -> (B, K)
            return carry + (jnp.transpose(toks, (1, 0, 2)), counts.T)

        # donate the cache through prefill and the fused loop where the
        # backend supports it (CPU doesn't; donating there only warns).
        # Safe for prefill: the pooled leaves of the passed slot_cache
        # ARE the live pool (replaced by the returned one), while the
        # per-slot leaves are eager slices — merge_slot_cache never
        # reads the donated buffers.
        donate = () if jax.default_backend() == "cpu" else (1,)

        def scoped(fn):
            """Run a compiled step under the serve topology so trace-time
            dispatch (expert-parallel MoE shard_map, paged activation
            constraints) sees the mesh.  Host path: identity."""
            if self._topo is None:
                return fn

            def run(*a):
                with shctx.serve_topology(self._topo):
                    return fn(*a)
            return run

        self._programs = {"prefill_chunk": prefill_chunk_fn,
                          "prefill_last": prefill_last_fn,
                          "decode": decode_loop_fn}
        self._prefill_fn = scoped(
            jax.jit(prefill_chunk_fn, donate_argnums=donate))
        self._prefill_last_fn = scoped(
            jax.jit(prefill_last_fn, donate_argnums=donate))
        self._decode_jit = jax.jit(decode_loop_fn, donate_argnums=donate)
        self._decode_fn = scoped(self._decode_jit)
        self._spec_decode_fn = (
            scoped(jax.jit(spec_loop_fn, donate_argnums=donate))
            if spec_k else None)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *, priority: int = 0,
               tenant: Optional[str] = None) -> int:
        """Queue one request; returns its uid.  Non-blocking: no device
        work happens until ``run()``/``tick()``.  Higher ``priority``
        admits first (submit order within a class); ``tenant`` keys the
        per-tenant quota."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            # reject HERE: admitted-then-failed would leak the slot's
            # pages (kv.free only runs at retirement)
            raise ValueError("empty prompt (need >= 1 token to prefill)")
        if len(prompt) + max_new_tokens + self._chunk_slack > self.max_len:
            # the slack term covers decode-tick overshoot — and, under
            # spec_decode, rejected-draft writes past the last accepted
            # position — so the fused loop can NEVER write beyond the
            # slot's allocated pages
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new_tokens}) + "
                f"decode slack ({self._chunk_slack}"
                f"{', spec_decode' if self.spec_decode else ''}) exceeds "
                f"max_len={self.max_len}")
        uid = self._uid
        self._uid += 1
        req = ServeRequest(uid, prompt, max_new_tokens, priority=priority,
                           tenant=tenant, t_submit=time.perf_counter())
        heapq.heappush(self._pending, (-priority, uid, req))
        self._byuid[uid] = req
        return uid

    def request(self, uid: int) -> ServeRequest:
        """Live view of a submitted request (the streaming front door
        reads ``req.out`` incrementally as ticks sync); valid until the
        request's result is handed off."""
        return self._byuid[uid]

    def tick(self) -> bool:
        """One scheduling quantum: an admission pass, then — if any
        slot is active — ONE fused decode tick (one dispatch + one host
        sync).  This is the streaming front door's pump.  Returns
        whether work remains (pending or active)."""
        with _span("sched.tick", pending=len(self._pending),
                   active=len(self._active)):
            admitted = self._admit()
            if self._active:
                self._decode_tick()
            elif self._pending and not admitted:
                # nothing active and nothing admissible: the best
                # pending request can never be served, even after prefix
                # eviction
                req = min(self._pending)[2]
                raise MemoryError(
                    f"request {req.uid} ({len(req.prompt)} prompt "
                    f"tokens) cannot be admitted into an empty batch — "
                    f"pool too small ({self.kv.free_pages} free pages)")
        return bool(self._active or self._pending)

    def take_results(self) -> Dict[int, ServeRequest]:
        """Hand off completed requests (and drop the uid index — a
        long-lived scheduler does not accumulate request arrays)."""
        done, self._results = self._results, {}
        for uid in done:
            self._byuid.pop(uid, None)
        return done

    def run(self) -> Dict[int, np.ndarray]:
        """Drain the queue; returns {uid: generated tokens} for the
        requests completed by THIS drain.  The TTFT stats window resets
        here: ``stats()["ttft_s"]`` covers one drain, never re-reports
        earlier requests (cumulative counters keep the lifetime view).
        """
        self._ttft = []
        while self.tick():
            pass
        return {uid: np.asarray(r.out, np.int32)
                for uid, r in self.take_results().items()}

    def generate(self, prompts: Sequence, max_new_tokens: int):
        """Convenience facade: submit all, run, return outputs in
        submit order (list of 1-D int32 arrays)."""
        uids = [self.submit(p, max_new_tokens) for p in prompts]
        results = self.run()
        return [results[u] for u in uids]

    def lower_decode(self):
        """The fused decode loop lowered at the engine's current state —
        ``.compile()`` it to inspect the program a decode tick runs (its
        memory, and whether the Pallas kernel is in it)."""
        return self.lower("decode", self.params, self.kv.cache,
                          self.kv.table(), self._tok, self._pos, self._done,
                          self._key)

    def lower(self, program: str, *args):
        """Lower one step — ``"prefill_chunk"``, ``"prefill_last"`` or
        ``"decode"`` — at ``args`` (``(params, cache, ...)`` as the
        scheduler passes them; ``jax.ShapeDtypeStruct``s placed on the
        devices to compile for will do), under the serve topology and
        with the cache donated as on a chip, whatever the default
        backend.  The programs close over the configuration, page size
        and decode chunk, not over the pool's size."""
        fn = jax.jit(self._programs[program], donate_argnums=(1,))
        with shctx.serve_topology(self._topo):
            return fn.lower(*args)

    def stats(self) -> dict:
        st = {
            "host_syncs": self.host_syncs,
            "dispatches": self.dispatches,
            "prefill_dispatches": self.prefill_dispatches,
            "prefill_host_syncs": self.prefill_host_syncs,
            "decode_dispatches": self.decode_dispatches,
            "decode_host_syncs": self.decode_host_syncs,
            "tokens_out": self.tokens_out,
            "syncs_per_token": (self.host_syncs / self.tokens_out
                                if self.tokens_out else 0.0),
            "ttft_s": list(self._ttft),          # window: last/current run
            "ttft_count_cum": self._ttft_n_cum,  # lifetime counters
            "ttft_sum_cum_s": self._ttft_sum_cum,
            "admitted": self.admitted,
            "queue_wait_s_sum": self.queue_wait_s_sum,
            "decode_lane_steps": self.decode_lane_steps,
            "decode_live_lane_steps": self.decode_live_lane_steps,
            "decode_occupancy": (self.decode_live_lane_steps
                                 / self.decode_lane_steps
                                 if self.decode_lane_steps else 0.0),
            "prompt_tokens": self.prompt_tokens,
            "prefix_hit_tokens": self.prefix_tokens_saved,
            "prefix_hit_rate": (self.prefix_tokens_saved
                                / self.prompt_tokens
                                if self.prompt_tokens else 0.0),
            "pool_pages_in_use": self.kv.pages_in_use,
            "pool_bytes": self.kv.pool_bytes(),
            "pool_bytes_per_device": self.kv.pool_bytes_per_device(),
            "slab_bytes_equiv": self.kv.slab_bytes(),
        }
        if self.prefix is not None:
            st["prefix_cache"] = self.prefix.stats()
        if self.spec_decode:
            steps = self.spec_verify_steps
            st["spec_decode"] = {
                "k": self.spec_decode,
                "verify_steps": steps,
                "draft_tokens": self.spec_draft_tokens,
                "accepted_tokens": self.spec_accepted_tokens,
                # acceptance = accepted / offered drafts (budget- and
                # EOS-truncated steps count what the host consumed)
                "acceptance": (self.spec_accepted_tokens
                               / self.spec_draft_tokens
                               if self.spec_draft_tokens else 0.0),
                # emitted tokens per verify step = 1 + accepted
                "tokens_per_step": ((self.spec_accepted_tokens + steps)
                                    / steps if steps else 0.0),
                "slot_verify_steps": self._spec_slot_steps.tolist(),
                "slot_accepted_tokens":
                    self._spec_slot_accepted.tolist(),
                "slot_accepted_len": [
                    1.0 + (a / s) if s else 0.0
                    for a, s in zip(self._spec_slot_accepted,
                                    self._spec_slot_steps)],
            }
        return st

    # ------------------------------------------------------------------
    # scheduling internals
    # ------------------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [s for s in range(self.slots) if s not in self._active]

    def _quota_of(self, tenant) -> Optional[int]:
        q = self.tenant_quota
        if q is None:
            return None
        if isinstance(q, dict):
            v = q.get(tenant)
            return None if v is None else int(v)
        return int(q)

    def _at_quota(self, tenant) -> bool:
        q = self._quota_of(tenant)
        if q is None:
            return False
        return sum(1 for r in self._active.values()
                   if r.tenant == tenant) >= q

    def _next_admissible(self) -> Optional[ServeRequest]:
        """Pop the highest-priority pending request whose tenant is
        under quota; quota-blocked requests are skipped (put back), not
        head-of-line blockers."""
        blocked = []
        req = None
        while self._pending:
            item = heapq.heappop(self._pending)
            if self._at_quota(item[2].tenant):
                blocked.append(item)
                continue
            req = item[2]
            break
        for item in blocked:
            heapq.heappush(self._pending, item)
        return req

    def _admit(self) -> int:
        """Admit queued requests into free slots in priority order.
        The free-slot set is recomputed every iteration: a prefill that
        retires at its very first token (EOS, or a 1-token budget)
        frees its slot MID-PASS, and the next queued request admits
        this tick instead of waiting out a full decode chunk.  Returns
        #admitted."""
        n = 0
        with _span("sched.admit") as span:
            while self._pending:
                free = self._free_slots()
                if not free:
                    break
                req = self._next_admissible()
                if req is None:                 # everything quota-blocked
                    break
                if not self._try_admit(free[0], req):
                    # pool pressure: the BEST admissible request waits,
                    # and nothing below it may jump the page queue
                    heapq.heappush(self._pending,
                                   (-req.priority, req.uid, req))
                    break
                n += 1
            span.set_metadata(admitted=n)
        return n

    def _try_admit(self, slot: int, req: ServeRequest) -> bool:
        """Alias + COW-fork + alloc + prefill one request into `slot`.
        Returns False (slot left clean) when the pool lacks pages even
        after prefix eviction."""
        S = len(req.prompt)
        matched, pages = (self.prefix.match(req.prompt)
                          if self.prefix is not None else (0, []))
        # always prefill >= 1 token — the last chunk's logits seed the
        # first sampled token
        start = min(matched, S - 1)
        # a fully-matched page-aligned prompt must re-write its final
        # token into a page it shares: copy-on-write fork of that page
        fork = bool(pages) and matched >= S
        total = self.kv.pages_needed(S + req.max_new_tokens
                                     + self._chunk_slack)
        fresh = total - len(pages) + (1 if fork else 0)
        # alias FIRST: the matched pages are now referenced by the slot,
        # so evicting their radix nodes below cannot free them under us
        self.kv.alias(slot, pages)
        if fresh > self.kv.free_pages and self.prefix is not None:
            self.prefix.evict(fresh)
        if fresh > self.kv.free_pages:
            self.kv.free(slot)              # roll the aliases back
            return False
        if fork:
            self.kv.cow_fork(slot, len(pages) - 1)
        self.kv.alloc(slot, S + req.max_new_tokens + self._chunk_slack)
        self.kv.reset_slot_state(slot)
        req.prefix_tokens = start
        self.prefix_tokens_saved += start
        self.prompt_tokens += S
        self._prefill(slot, req, start)
        return True

    def _prefill(self, slot: int, req: ServeRequest, start: int = 0):
        queued = time.perf_counter() - req.t_submit
        self.admitted += 1
        self.queue_wait_s_sum += queued
        C = self.prefill_chunk
        S = len(req.prompt)
        starts = list(range(start, S, C))      # non-empty: start <= S-1
        with _span("sched.prefill", uid=req.uid, prompt_len=S,
                   prefix_tokens=start, chunks=len(starts),
                   queued_ms=1e3 * queued):
            table_row = self.kv.table([slot])
            for s in starts[:-1]:
                chunk = jnp.asarray(req.prompt[None, s:s + C])
                cache, _ = self._prefill_fn(
                    self.params, self.kv.slot_cache(slot), table_row, chunk,
                    jnp.full((1,), s, jnp.int32))
                self.kv.merge_slot_cache(slot, cache)
                self.dispatches += 1
                self.prefill_dispatches += 1
            # last chunk: sampling fused into the same compiled call —
            # no separate first-token launch
            s = starts[-1]
            self._key, sub = jax.random.split(self._key)
            chunk = jnp.asarray(req.prompt[None, s:s + C])
            cache, first_dev, h_last = self._prefill_last_fn(
                self.params, self.kv.slot_cache(slot), table_row, chunk,
                jnp.full((1,), s, jnp.int32), sub)
            self.kv.merge_slot_cache(slot, cache)
            self.dispatches += 1
            self.prefill_dispatches += 1
            if self.prefix is not None:
                # index the prompt's FULL pages (decode never writes them:
                # its first write position S lands in the next block)
                full = S // self.kv.page_size
                if full:
                    self.prefix.insert(req.prompt, self.kv._owned[slot][:full])
            with _span("sched.prefill.sync"):
                first = int(first_dev)             # prefill's ONE host sync
            self.host_syncs += 1
            self.prefill_host_syncs += 1
            req.t_first = time.perf_counter()
            req.out.append(first)
            self.tokens_out += 1
            if (self.eos_id is not None and first == self.eos_id) \
                    or req.max_new_tokens <= 1:
                self._retire(slot, req, active=False)
                return
            self._active[slot] = req
            self._tok = self._tok.at[slot].set(first)
            self._pos = self._pos.at[slot].set(S)
            if self.spec_decode:
                # seed the draft head: trunk hidden at the last prompt
                # position pairs with `first` exactly like train-mode MTP
                self._hid = self._hid.at[slot].set(h_last[0])
            self._done_host[slot] = False
            self._done = jnp.asarray(self._done_host)

    def _retire(self, slot: int, req: ServeRequest, *, active=True):
        req.t_done = time.perf_counter()
        if req.ttft is not None:
            self._ttft.append(req.ttft)
            self._ttft_n_cum += 1
            self._ttft_sum_cum += req.ttft
        self.kv.free(slot)
        if active:
            del self._active[slot]
        self._done_host[slot] = True
        self._done = jnp.asarray(self._done_host)
        self._results[req.uid] = req

    def _decode_tick(self):
        """One fused decode tick: ONE dispatch and ONE host sync for
        ``decode_chunk`` scan steps, then the host walks the tokens."""
        live, steps = len(self._active), self.decode_chunk
        self.decode_lane_steps += self.slots * steps
        self.decode_live_lane_steps += live * steps
        with _span("sched.decode", live=live, slots=self.slots,
                   steps=steps):
            with _span("sched.decode.dispatch"):
                pulled = (self._spec_dispatch() if self.spec_decode
                          else self._dispatch())
            self.dispatches += 1
            self.decode_dispatches += 1
            with _span("sched.decode.sync"):
                pulled = [np.asarray(a) for a in pulled]  # ONE sync/tick
            self.host_syncs += 1
            self.decode_host_syncs += 1
            with _span("sched.decode.emit"):
                if self.spec_decode:
                    self._spec_emit(*pulled)
                else:
                    self._emit(*pulled)
                # device `done` may be ahead of host bookkeeping (EOS
                # slots we also retired); re-sync the mirror we own
                self._done = jnp.asarray(self._done_host)

    def _dispatch(self):
        out = self._decode_fn(self.params, self.kv.cache, self.kv.table(),
                              self._tok, self._pos, self._done, self._key)
        self.kv.cache, self._tok, self._pos, self._done, self._key, toks = out
        return (toks,)

    def _emit(self, toks_np):
        for slot, req in list(self._active.items()):
            finished = False
            for t in toks_np[slot]:
                req.out.append(int(t))
                self.tokens_out += 1
                if self.eos_id is not None and t == self.eos_id:
                    finished = True
                    break
                if len(req.out) >= req.max_new_tokens:
                    finished = True
                    break
            if finished:
                self._retire(slot, req)

    def _spec_dispatch(self):
        """The speculative twin of ``_dispatch``: each of the
        ``decode_chunk`` scan steps emits 1..k tokens.  ``toks`` is
        (slots, K, k) with each step's emitted tokens left-packed;
        ``counts`` (slots, K) says how many are real (0 on done/idle
        lanes)."""
        out = self._spec_decode_fn(
            self.params, self.kv.cache, self.kv.table(), self._tok,
            self._pos, self._hid, self._done)
        (self.kv.cache, self._tok, self._pos, self._hid, self._done,
         toks, counts) = out
        return toks, counts

    def _spec_emit(self, toks_np, counts_np):
        k = self.spec_decode
        for slot, req in list(self._active.items()):
            finished = False
            for step in range(counts_np.shape[1]):
                cnt = int(counts_np[slot, step])
                if cnt <= 0:            # lane went done in a prior step
                    break
                req.spec_steps += 1
                req.spec_accepted += cnt - 1
                self.spec_verify_steps += 1
                self.spec_draft_tokens += k - 1
                self.spec_accepted_tokens += cnt - 1
                self._spec_slot_steps[slot] += 1
                self._spec_slot_accepted[slot] += cnt - 1
                for t in toks_np[slot, step, :cnt]:
                    req.out.append(int(t))
                    self.tokens_out += 1
                    if self.eos_id is not None and t == self.eos_id:
                        finished = True
                        break
                    if len(req.out) >= req.max_new_tokens:
                        finished = True
                        break
                if finished:
                    break
            if finished:
                self._retire(slot, req)
