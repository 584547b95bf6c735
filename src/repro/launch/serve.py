"""Serving launcher: continuous batching by default, checkpoint-backed.

    # serve fresh random weights (smoke config) with the paged engine
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --reduced --batch 4 --prompt-len 32 --new-tokens 16

    # close the train-and-serve loop: serve what train.py checkpointed
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --reduced --dp-strategy zero1 --steps 50 --ckpt /tmp/ck
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --reduced --restore /tmp/ck

``--reduced`` picks the smoke widths of the architecture and nothing
else; the published config is the default.  Either is served in the
config's compute dtype on the devices present: one device serves
without a mesh, several serve on a ``1 x n`` (data, model) mesh by the
CONTINUOUS engine — the paged pool is model-sharded over the mesh
(``sharding.rules.pool_spec``), params land with the serve-mode
shardings, and MoE decode routes through the expert-parallel
``shard_map``.  ``--mesh-shape DxM`` picks another layout of the
devices present.  The mesh is threaded INTO the engine
(``make_engine(..., mesh=)``) and every compiled call runs under a
scoped serve topology, so in-process callers never inherit device
state from this launcher.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHITECTURES, get_config, smoke_config
from repro.data import synthetic_tokens
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_serve_mesh
from repro.models import init_model
from repro.serve import (FrontDoor, SamplingConfig, make_engine,
                         make_engine_from_checkpoint)
from repro.serve.scheduler import ContinuousScheduler
from repro.sharding.ctx import activation_mesh


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHITECTURES))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the smoke widths of --arch")
    ap.add_argument("--batch", type=int, default=4,
                    help="serving slots (decode batch width)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=None,
                    help="continuous engine: total requests to submit "
                         "(default: --batch; > --batch exercises "
                         "admission on retirement)")
    ap.add_argument("--engine", default=None,
                    choices=["continuous", "legacy"],
                    help="default: continuous (the production path); "
                         "legacy is the lockstep slab reference")
    ap.add_argument("--mesh-shape", default=None, metavar="DxM",
                    help="serve mesh shape, e.g. 2x4 (data x model); "
                         "default: 1 x (devices present), no mesh on "
                         "one device")
    ap.add_argument("--restore", default="",
                    help="serve the params of this checkpoint dir "
                         "(written by launch/train.py — any sharded "
                         "layout, or legacy npz) instead of random init")
    ap.add_argument("--step", type=int, default=None,
                    help="checkpoint step (default: latest)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--decode-kernel", default=None,
                    choices=["xla", "pallas"],
                    help="paged decode attention: xla (gather + masked "
                         "softmax reference) or pallas (fused page-"
                         "table-gather flash kernel; interpreted on "
                         "the CPU).  Default: the arch config's setting")
    ap.add_argument("--report", action="store_true",
                    help="print the dispatch-discipline report: per-"
                         "phase (prefill/decode) compiled-call and "
                         "host-sync counters from the scheduler")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="continuous engine: speculative decode with "
                         "K-token verify chunks (the carried token + "
                         "K-1 MTP drafts per fused-loop step).  Greedy-"
                         "only, needs an arch with cfg.mtp_depth > 0; "
                         "outputs stay bitwise-equal to K=0")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous engine: radix prefix cache — "
                         "shared prompt prefixes alias already-written "
                         "KV pages instead of re-prefilling")
    ap.add_argument("--stream", action="store_true",
                    help="continuous engine: serve through the async "
                         "front door, printing each request's tokens "
                         "as its decode chunks sync")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling PRNG seed")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--eos-id", type=int, default=None)
    args = ap.parse_args(argv)

    # resolve the request count ONCE, up front: None and the legacy 0
    # sentinel both mean "--batch requests" — every later consumer
    # (submission, the legacy-engine bound, the report line) sees the
    # resolved value, never the sentinel
    if not args.requests:
        args.requests = args.batch

    cfg = smoke_config(args.arch) if args.reduced else get_config(args.arch)
    dtype = jnp.dtype(cfg.dtype)
    if args.decode_kernel:
        cfg = cfg.with_overrides(decode_kernel=args.decode_kernel)
    if args.mesh_shape:
        try:
            d, m = (int(v) for v in args.mesh_shape.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--mesh-shape {args.mesh_shape!r}: "
                             "expected DxM, e.g. 2x4")
        mesh = make_serve_mesh(d, m)
    else:
        n = jax.device_count()
        mesh = make_serve_mesh(1, n) if n > 1 else None
    if cfg.is_encoder_decoder or cfg.frontend != "none":
        raise SystemExit("serve launcher drives decoder-only archs; "
                         "see examples/ for VLM / enc-dec handling")

    engine = args.engine or "continuous"
    if engine == "legacy" and (args.prefix_cache or args.stream
                               or args.spec_decode):
        raise SystemExit("--prefix-cache/--stream/--spec-decode are "
                         "continuous-engine features (the lockstep slab "
                         "has neither a page table to alias, a queue to "
                         "stream from, nor a fused loop to widen)")
    if engine == "legacy" and args.requests > args.batch:
        raise SystemExit(
            f"--requests {args.requests} > --batch {args.batch}: the "
            "legacy lockstep engine has no queue (all slots start and "
            "retire together); use the continuous engine or raise "
            "--batch")

    sampling = SamplingConfig(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p)
    # decode-overshoot slack: one decode chunk of 8 normally; under
    # spec decode each of those steps may write a K-token verify chunk
    # (plus K rejected-draft positions) into allocated pages
    slack = 8 * args.spec_decode + args.spec_decode if args.spec_decode \
        else 8
    max_len = -(-(args.prompt_len + args.new_tokens + slack)
                // args.page_size) * args.page_size
    engine_kw = dict(engine=engine, batch_size=args.batch,
                     max_len=max_len, dtype=dtype, eos_id=args.eos_id,
                     sampling=sampling, seed=args.seed, mesh=mesh)
    if engine == "continuous":
        engine_kw["page_size"] = args.page_size
        engine_kw["prefix_cache"] = args.prefix_cache
        if args.spec_decode:
            engine_kw["spec_decode"] = args.spec_decode

    key = jax.random.PRNGKey(args.seed)
    # the activation mesh is SCOPED: nothing leaks into in-process
    # callers after this returns, and the mesh-free paths explicitly
    # run mesh-free even if a previous caller left one set (the
    # engines additionally scope the serve topology per compiled call)
    with activation_mesh(mesh):
        if args.restore:
            eng = make_engine_from_checkpoint(args.restore, cfg,
                                              step=args.step, **engine_kw)
            print(f"serving checkpoint step {eng.restored_step} "
                  f"from {args.restore}")
        else:
            eng = make_engine(cfg, init_model(cfg, key), **engine_kw)

        n_req = args.requests
        prompts = synthetic_tokens(key, n_req, args.prompt_len,
                                   cfg.vocab_size)
        t0 = time.time()
        if isinstance(eng, ContinuousScheduler):
            if args.stream:
                fd = FrontDoor(eng)
                handles = [fd.submit(p, args.new_tokens)
                           for p in np.asarray(prompts)]
                outs = []
                for i, h in enumerate(handles):
                    toks = list(h)     # pumps; tokens print as they sync
                    print(f"req {i} (ttft {h.ttft * 1e3:.0f}ms): {toks}")
                    outs.append(toks)
            else:
                outs = [o.tolist() for o in
                        eng.generate(list(np.asarray(prompts)),
                                     args.new_tokens)]
            dt = time.time() - t0
            n_tok = sum(len(o) for o in outs)
            st = eng.stats()
            extra = (f", prefix hit rate {st['prefix_hit_rate']:.0%}"
                     if args.prefix_cache else "")
            if args.spec_decode:
                sd = st["spec_decode"]
                extra += (f", spec k={sd['k']} acceptance "
                          f"{sd['acceptance']:.0%} "
                          f"({sd['tokens_per_step']:.2f} tok/verify)")
            print(f"{n_req} requests x {args.new_tokens} tokens in "
                  f"{dt:.2f}s ({n_tok/dt:.1f} tok/s incl. compile, "
                  f"{st['syncs_per_token']:.3f} host syncs/token, "
                  f"pool {st['pool_pages_in_use']} pages live, "
                  f"{st['pool_bytes_per_device']} pool bytes/device"
                  f"{extra})")
            if args.report:
                # dispatch discipline per phase: prefill = chunk
                # scatters with the first-token sample fused into the
                # last one (1 sync/request); decode = fused chunk loops
                # (1 sync/decode_chunk tokens)
                print(f"report: decode_kernel={cfg.decode_kernel} "
                      f"prefill {st['prefill_dispatches']} dispatches / "
                      f"{st['prefill_host_syncs']} host syncs "
                      f"({st['prefill_host_syncs'] / n_req:.2f} "
                      f"syncs/request), "
                      f"decode {st['decode_dispatches']} dispatches / "
                      f"{st['decode_host_syncs']} host syncs "
                      f"({st['decode_host_syncs'] / max(1, n_tok):.3f} "
                      f"syncs/token), queue wait "
                      f"{st['queue_wait_s_sum'] / max(1, st['admitted']):.4f}"
                      f" s mean over {st['admitted']} admitted, decode "
                      f"occupancy {st['decode_occupancy']:.0%}")
        else:
            out = eng.generate(prompts[:args.batch], args.new_tokens)
            dt = time.time() - t0
            print(f"{args.batch} seqs x {args.new_tokens} tokens in "
                  f"{dt:.2f}s "
                  f"({args.batch*args.new_tokens/dt:.1f} tok/s incl. "
                  f"compile)")
            if args.report:
                # the lockstep slab has no phase split — one prefill
                # dispatch, then a blocking round-trip per token
                spt = eng.host_syncs / (args.batch * args.new_tokens)
                print(f"report: legacy {eng.dispatches} dispatches / "
                      f"{eng.host_syncs} host syncs "
                      f"({spt:.3f} syncs/token)")
            outs = np.asarray(out).tolist()
        print(outs)
    return outs


if __name__ == "__main__":
    main()
