"""Compile guards for the TPU path: the main-path Pallas kernels and one
full-width paged decode step, compiled for a described TPU v5e (no
chip attached, nothing runs).  The TPU compiler refuses what interpret
mode accepts — block shapes off the (8, 128) tiling, layouts Mosaic
cannot lower, programs that do not fit 16 GiB of HBM — so these catch
it at no chip time."""
import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_decode import (paged_flash_decode,
                                        paged_flash_decode_mla)

HBM_BYTES = 16 * 2 ** 30                     # TPU v5e
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-topology compile is written to the persistent cache
    but cannot be read back without a chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("seq", [1, 32], ids=["decode", "prefill-chunk"])
def test_gqa_paged_decode_compiles_at_qwen3_widths(one_chip,
                                                   no_persistent_cache, seq):
    cfg = get_config("qwen3-1.7b")
    B, W = 8, 32
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    N = (B * W + 1) * PAGE

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = _compile(
        functools.partial(paged_flash_decode, page_size=PAGE),
        s((B, seq, h, hd), jnp.bfloat16), s((N, hk, hd), jnp.bfloat16),
        s((N, hk, hd), jnp.bfloat16), s((B, W), jnp.int32),
        s((B, seq), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_mla_paged_decode_compiles_at_deepseek_v3_widths(
        one_chip, no_persistent_cache):
    cfg = get_config("deepseek-v3-671b")
    m = cfg.mla
    B, W, S = 8, 32, 1
    N = (B * W + 1) * PAGE

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    compiled = _compile(
        functools.partial(paged_flash_decode_mla, page_size=PAGE,
                          scale=scale),
        s((B, S, cfg.num_heads, m.kv_lora_rank), jnp.bfloat16),
        s((B, S, cfg.num_heads, m.qk_rope_head_dim), jnp.bfloat16),
        s((N, m.kv_lora_rank), jnp.bfloat16),
        s((N, m.qk_rope_head_dim), jnp.bfloat16),
        s((B, W), jnp.int32), s((B, S), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_qwen3_paged_decode_step_compiles_with_the_kernel(
        one_chip, no_persistent_cache):
    """One paged decode step of the whole published qwen3-1.7b (bf16
    weights, 8 slots x 512 tokens of paged KV) through ``apply_model``:
    the Pallas kernel is in it and the program fits one chip."""
    from repro.models import apply_model, init_cache, init_model
    from repro.models.attention import PagedView

    cfg = get_config("qwen3-1.7b").with_overrides(decode_kernel="pallas")
    slots, max_len = 8, 512
    W = max_len // PAGE
    n_pages = slots * W + 1

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                           sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(functools.partial(init_model, cfg),
                                    jax.random.PRNGKey(0)), jnp.bfloat16)
    cache = on_chip(jax.eval_shape(functools.partial(
        init_cache, cfg, slots, max_len, jnp.bfloat16,
        pool=(n_pages, PAGE))))

    def step(params, cache, table, tok, pos):
        out = apply_model(cfg, params, {"tokens": tok}, mode="decode",
                          cache=cache, cache_pos=pos,
                          paged=PagedView(table, PAGE))
        return out["logits"][:, -1], out["cache"]

    compiled = _compile(
        step, params, cache,
        jax.ShapeDtypeStruct((slots, W), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


# the chat cell's serving shapes: 32 slots, a 39232-token pool per layer
# (2452 pages of 16), page tables 321 pages wide (5136 tokens)
CHAT_SLOTS, CHAT_PAGES, CHAT_WIDTH = 32, 2452, 321
CHAT_LAYERS = 4
# program -> (decode kernel, scheduler step, the step's arguments after
# (params, cache)); the last chunk is a ragged tail of 17 tokens
CHAT_PROGRAMS = {
    "decode": ("xla", "decode"),
    "decode-pallas": ("pallas", "decode"),
    "prefill-chunk": ("xla", "prefill_chunk"),
    "prefill-last": ("xla", "prefill_last"),
}


def _chat_args(step, s, slots=CHAT_SLOTS, width=CHAT_WIDTH):
    if step == "decode":
        return (s((slots, width)), s((slots, 1)), s((slots,)),
                s((slots,), jnp.bool_), s((2,), jnp.uint32))
    tokens = 32 if step == "prefill_chunk" else 17
    args = (s((1, width)), s((1, tokens)), s((1,)))
    return args + ((s((2,), jnp.uint32),) if step == "prefill_last" else ())


def _moves(text, ops, size):
    """The instructions among ``ops`` whose (array) result's element
    count, whatever its shape, ``size`` picks."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(", line)
        if m and m.group(2) in ops and size(math.prod(
                int(d) for d in m.group(1).split(",") if d)):
            out.append(line.strip()[:160])
    return out


MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice",
         "all-gather", "all-gather-start")


@pytest.fixture(scope="module")
def chat_compile(one_chip, no_persistent_cache):
    """The scheduler's own steps, compiled for one v5e at qwen3-1.7b
    widths and the chat cell's pool, with the donation they get on a
    chip; compiled once per program, on first use.  Cut to 4 layers to
    keep the compiles short: the layer scan's body is one program
    whatever the depth, and 4 keeps the stacked pool leaf's size apart
    from a per-layer one's.  Returns ``get(program) -> (compiled, pool
    leaf)``."""
    from repro.models import init_cache, init_model
    from repro.serve import ContinuousScheduler

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                           sharding=one_chip), tree)

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    done = {}

    def get(program):
        if program not in done:
            kernel, step = CHAT_PROGRAMS[program]
            cfg = get_config("qwen3-1.7b").with_overrides(
                num_layers=CHAT_LAYERS, decode_kernel=kernel)
            params = on_chip(jax.eval_shape(
                functools.partial(init_model, cfg), jax.random.PRNGKey(0)),
                jnp.bfloat16)
            # the steps close over the config and page size only: a
            # two-page scheduler builds them, lowered at the chat shapes
            sched = ContinuousScheduler(
                cfg, params, slots=1, max_len=PAGE, num_pages=2,
                dtype=jnp.bfloat16, page_size=PAGE, prefill_chunk=32,
                decode_chunk=8)
            cache = on_chip(jax.eval_shape(functools.partial(
                init_cache, cfg, CHAT_SLOTS, CHAT_WIDTH * PAGE,
                jnp.bfloat16, pool=(CHAT_PAGES, PAGE))))
            compiled = sched.lower(step, params, cache,
                                   *_chat_args(step, s)).compile()
            done[program] = compiled, jax.tree_util.tree_leaves(cache)[0]
        return done[program]
    return get


@pytest.mark.parametrize("program", list(CHAT_PROGRAMS))
def test_chat_programs_update_the_pool_in_place(chat_compile, program):
    """The paged pool rides the layer scan's carry: no instruction
    copies, slices or re-stacks a whole pool leaf, per layer or stacked,
    in any shape.  What is left of the pool's size is the in-place
    scatter of the new tokens (fusions of the stacked leaf's size).
    The Pallas kernel's page view folds the heads into the lane dim, a
    relayout of what it is given: one layer's pool, sliced out first
    (as its every layer did before the carry), never the stack."""
    compiled, leaf = chat_compile(program)
    assert leaf.shape == (CHAT_LAYERS, CHAT_PAGES * PAGE, 8, 128)
    stacked, per_layer = leaf.size, leaf.size // CHAT_LAYERS
    text = compiled.as_text()
    if program == "decode-pallas":
        assert "tpu_custom_call" in text
        bad = _moves(text, MOVES + ("reshape",), lambda n: n == stacked)
    else:
        bad = (_moves(text, MOVES, lambda n: n in (stacked, per_layer))
               + _moves(text, ("fusion",), lambda n: n == per_layer))
    assert not bad, "\n".join(bad)


@pytest.mark.parametrize("program", list(CHAT_PROGRAMS))
def test_chat_decode_holds_no_second_pool(chat_compile, program):
    """No step holds a second copy of the pool: the decode loops'
    temporaries stay below the pool's (K and V) bytes, the prefill
    chunks' below one layer's K."""
    compiled, leaf = chat_compile(program)
    leaf_bytes = leaf.size * leaf.dtype.itemsize
    limit = (2 * leaf_bytes if program.startswith("decode")
             else leaf_bytes // CHAT_LAYERS)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < limit, (temp, limit)


# the serve mesh on the four chips of a host: "model" over all four
MESH_LAYERS = 8


def test_mesh_decode_reads_one_layer_of_the_pool(topo, no_persistent_cache):
    """One paged decode step at qwen3-1.7b widths and the chat cell's
    pool on a (1, 4) serve mesh (each chip holds 2 of the 8 kv heads of
    every token): the XLA read slices its layer's rows out of the
    stacked pool (the per-device layout is not the gather's), so no
    instruction copies, slices or all-gathers a stacked leaf (a chip's
    share or the whole), and the temporaries stay below one stacked
    leaf's share.  8 layers keep
    a stacked share apart from a per-layer leaf's size.  (The Pallas
    kernels cannot be partitioned onto a TPU mesh: they run there only
    in interpret mode on the CPU.)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.models import apply_model, init_cache, init_model
    from repro.models.attention import PagedView
    from repro.sharding import ctx as shctx
    from repro.sharding.rules import (ShardingConfig, param_shardings,
                                      pool_shardings)

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    cfg = get_config("qwen3-1.7b").with_overrides(num_layers=MESH_LAYERS)

    def placed(shapes, shardings, dtype=None):
        return jax.tree_util.tree_map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                               sharding=sh),
            shapes, shardings)
    pshapes = jax.eval_shape(functools.partial(init_model, cfg),
                             jax.random.PRNGKey(0))
    params = placed(pshapes, param_shardings(
        cfg, mesh, pshapes, ShardingConfig.for_mode("serve")),
        jnp.bfloat16)
    cshapes = jax.eval_shape(functools.partial(
        init_cache, cfg, CHAT_SLOTS, CHAT_WIDTH * PAGE, jnp.bfloat16,
        pool=(CHAT_PAGES, PAGE)))
    pool_sh = pool_shardings(cfg, mesh, cshapes, jax.tree_util.tree_map(
        lambda _: -1, cshapes))
    cache = placed(cshapes, pool_sh)
    rep = NamedSharding(mesh, PartitionSpec())

    def step(params, cache, table, tok, pos):
        out = apply_model(cfg, params, {"tokens": tok}, mode="decode",
                          cache=cache, cache_pos=pos,
                          paged=PagedView(table, PAGE))
        return (jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                       out["cache"], pool_sh),
                out["logits"][:, -1])

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
    with shctx.serve_topology(shctx.ServeTopology.from_mesh(mesh)):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, s((CHAT_SLOTS, CHAT_WIDTH)), s((CHAT_SLOTS, 1)),
            s((CHAT_SLOTS,))).compile()
    leaf = jax.tree_util.tree_leaves(cshapes["blocks"])[0]
    assert leaf.shape == (MESH_LAYERS, CHAT_PAGES * PAGE, 8, 128)
    share = leaf.size // 4                      # one stacked leaf's, per chip
    big = _moves(compiled.as_text(), MOVES,
                 lambda n: n in (share, leaf.size))
    assert not big, "\n".join(big)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < share * leaf.dtype.itemsize, temp



# the deepseek-moe-16b batch cell's serving shapes: its first pipeline
# stage (the dense layer and 6 MoE layers of 64 + 2 experts, every
# expert held), 32 slots, page tables 129 pages wide (2064 tokens) and
# a 4129-page pool (every slot at its longest)
MOE_LAYERS, MOE_SLOTS, MOE_PAGES, MOE_WIDTH = 7, 32, 4129, 129
# spare HBM the benchmark keeps beside one program's arguments and
# temporaries: the other programs' temporaries and the allocator's slack
MOE_SPARE = 1.5e9


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_moe_batch_programs_fit_one_chip(one_chip, no_persistent_cache,
                                         step):
    """The batch cell's decode loop and prefill chunk at deepseek-moe-16b
    widths (capacity E/k, so C = N and no slot is dropped): the weights
    (8.06 GB), the pool (3.79 GB) and the step's temporaries fit one
    16-GB v5e with 1.5 GB to spare.  Measured (described v5e, JAX
    0.9.0): arguments 11849676800 bytes; temporaries 995850752 (decode
    loop) and 1994240 (prefill chunk); in all 12845527552 and
    11851638272."""
    from repro.models import init_cache, init_model
    from repro.serve import ContinuousScheduler

    cfg = get_config("deepseek-moe-16b")
    cfg = cfg.with_overrides(num_layers=MOE_LAYERS, moe=dataclasses.replace(
        cfg.moe, capacity_factor=10.67))

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                           sharding=one_chip), tree)

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = on_chip(jax.eval_shape(functools.partial(init_model, cfg),
                                    jax.random.PRNGKey(0)), jnp.bfloat16)
    sched = ContinuousScheduler(
        cfg, params, slots=1, max_len=PAGE, num_pages=2, dtype=jnp.bfloat16,
        page_size=PAGE, prefill_chunk=32, decode_chunk=8)
    cache = on_chip(jax.eval_shape(functools.partial(
        init_cache, cfg, MOE_SLOTS, MOE_WIDTH * PAGE, jnp.bfloat16,
        pool=(MOE_PAGES, PAGE))))
    compiled = sched.lower(step, params, cache, *_chat_args(
        step, s, MOE_SLOTS, MOE_WIDTH)).compile()
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < 16e9 - MOE_SPARE, used
