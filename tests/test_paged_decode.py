"""Paged flash-decode kernel tier: kernel-vs-oracle numerics, the
paged_read invariants the kernel's masking contract relies on, greedy
pallas==xla equality on host and on the (2, 4) serve mesh, the fused-
sampling dispatch discipline, and the perf-model calibration hooks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices

from repro.configs import smoke_config
from repro.core import perf_model
from repro.kernels.paged_decode import (paged_flash_decode,
                                        paged_flash_decode_mla)
from repro.models import apply_model, init_cache, init_model
from repro.models.attention import (PagedView, masked_attention,
                                    paged_read, _paged_append)
from repro.serve import ContinuousScheduler, make_engine

KEY = jax.random.PRNGKey(11)


def _cfg(arch="qwen3-1.7b", **kw):
    return smoke_config(arch).with_overrides(dtype="float32", **kw)


def _prompts(cfg, lengths, seed=0):
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + i), (L,), 0, cfg.vocab_size))
        for i, L in enumerate(lengths)]


# --------------------------------------------------------------------------
# kernel vs oracle (standalone, host)
# --------------------------------------------------------------------------

def _random_paged(key, B, W, ps, n_pages, feat, trash_fill=1e4):
    """A token-major pool with POISONED trash page (page 0) and poisoned
    unallocated pages, plus a per-slot table allocating a prefix of each
    row.  Returns (pool, table, alloc_pages per slot)."""
    ks = jax.random.split(key, 3)
    pool = jax.random.normal(ks[0], (n_pages * ps,) + feat, jnp.float32)
    # poison page 0 (trash) AND every never-referenced page: only the
    # mask keeps them out of the output
    pool = pool.at[:ps].set(trash_fill)
    alloc = [int(x) for x in
             jax.random.randint(ks[1], (B,), 1, W + 1)]           # >=1 page
    table = np.zeros((B, W), np.int32)
    nxt = 1
    for b in range(B):
        for w in range(alloc[b]):
            table[b, w] = nxt
            nxt += 1
    assert nxt <= n_pages
    return pool, jnp.asarray(table), alloc


GQA_CASES = [
    # B, S, h, hk, hd, ps, W, window
    (2, 1, 4, 2, 64, 16, 4, 0),        # decode step, GQA
    (3, 1, 4, 4, 32, 8, 5, 0),         # MHA
    (1, 12, 4, 1, 64, 16, 3, 0),       # prefill chunk, MQA
    (2, 7, 8, 2, 32, 8, 6, 20),        # sliding window
    (2, 5, 2, 2, 64, 32, 2, 0),        # big pages, ragged chunk
]


@pytest.mark.parametrize("case", GQA_CASES)
def test_gqa_kernel_matches_oracle(case):
    B, S, h, hk, hd, ps, W, window = case
    ks = jax.random.split(jax.random.fold_in(KEY, hash(case) % 997), 3)
    kp, table, alloc = _random_paged(ks[0], B, W, ps, W * B + 2, (hk, hd))
    vp, _, _ = _random_paged(ks[1], B, W, ps, W * B + 2, (hk, hd))
    vp = jnp.where(jnp.arange(vp.shape[0])[:, None, None] < ps, 1e4, vp)
    q = jax.random.normal(ks[2], (B, S, h, hd), jnp.float32)
    # each slot's positions live inside its allocated pages
    pos = jnp.asarray([[a * ps - S + s for s in range(S)]
                       for a in alloc], jnp.int32)
    view = PagedView(table, ps)
    k_full, kv_pos = paged_read(kp, view)
    v_full, _ = paged_read(vp, view)
    want = masked_attention(q, k_full, v_full, q_positions=pos,
                            kv_positions=kv_pos, window=window)
    got = paged_flash_decode(q, kp, vp, table, pos, page_size=ps,
                             window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


MLA_CASES = [
    # B, S, h, r, rope, ps, W, window
    (2, 1, 4, 32, 16, 16, 4, 0),
    (1, 9, 4, 32, 16, 8, 5, 0),
    (2, 4, 2, 64, 8, 8, 6, 24),
]


@pytest.mark.parametrize("case", MLA_CASES)
def test_mla_kernel_matches_oracle(case):
    B, S, h, r, rope, ps, W, window = case
    ks = jax.random.split(jax.random.fold_in(KEY, hash(case) % 991), 4)
    ckv, table, alloc = _random_paged(ks[0], B, W, ps, W * B + 2, (r,))
    krp, _, _ = _random_paged(ks[1], B, W, ps, W * B + 2, (rope,))
    q_lat = jax.random.normal(ks[2], (B, S, h, r), jnp.float32)
    q_rope = jax.random.normal(ks[3], (B, S, h, rope), jnp.float32)
    pos = jnp.asarray([[a * ps - S + s for s in range(S)]
                       for a in alloc], jnp.int32)
    scale = 0.125
    view = PagedView(table, ps)
    ckv_c, kv_pos = paged_read(ckv, view)
    krp_c, _ = paged_read(krp, view)
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, ckv_c)
              + jnp.einsum("bshk,btk->bhst", q_rope, krp_c)) * scale
    mask = kv_pos[None, None, :] <= pos[:, :, None]
    if window:
        mask &= kv_pos[None, None, :] > pos[:, :, None] - window
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    want = jnp.einsum("bhst,btr->bshr", probs, ckv_c)
    got = paged_flash_decode_mla(q_lat, q_rope, ckv, krp, table, pos,
                                 page_size=ps, scale=scale, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_kernel_trash_poison_never_leaks():
    """Flood the trash page and every unallocated page with 1e8: the
    kernel output must stay identical to the zero-filled-pool output —
    visibility masking alone isolates unwritten storage."""
    B, S, h, hk, hd, ps, W = 2, 3, 4, 2, 32, 8, 4
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, h, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (6 * ps, hk, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (6 * ps, hk, hd), jnp.float32)
    table = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([[2 * ps - S + s for s in range(S)],
                       [ps - S + s for s in range(S)]], jnp.int32)
    written = jnp.zeros((6 * ps,), bool).at[ps:4 * ps].set(True)
    clean = lambda p: jnp.where(written[:, None, None], p, 0.0)
    poison = lambda p: jnp.where(written[:, None, None], p, 1e8)
    a = paged_flash_decode(q, clean(kp), clean(vp), table, pos,
                           page_size=ps)
    b = paged_flash_decode(q, poison(kp), poison(vp), table, pos,
                           page_size=ps)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# paged_read invariants (the gather the kernel fuses away)
# --------------------------------------------------------------------------

def test_paged_read_page_granular_shape_and_content():
    ps, n_pages, feat = 4, 5, (2, 3)
    pool = jnp.arange(n_pages * ps * 6, dtype=jnp.float32).reshape(
        (n_pages * ps,) + feat)
    table = jnp.asarray([[2, 1, 0], [4, 0, 0]], jnp.int32)
    out, kv_pos = paged_read(pool, PagedView(table, ps))
    assert out.shape == (2, 3 * ps) + feat          # (B, W*ps, ...)
    np.testing.assert_array_equal(np.asarray(kv_pos), np.arange(3 * ps))
    pages = np.asarray(pool).reshape((n_pages, ps) + feat)
    # whole contiguous pages, in table order
    np.testing.assert_array_equal(np.asarray(out[0, :ps]), pages[2])
    np.testing.assert_array_equal(np.asarray(out[0, ps:2 * ps]), pages[1])
    np.testing.assert_array_equal(np.asarray(out[1, :ps]), pages[4])


def test_paged_read_unallocated_blocks_gather_trash_page():
    """Unallocated table entries (0) gather the trash page verbatim —
    they are only safe because the causal mask kills those positions,
    which the poison test above pins end to end."""
    ps = 4
    pool = jnp.zeros((3 * ps, 2), jnp.float32).at[:ps].set(7.0)
    table = jnp.asarray([[1, 0]], jnp.int32)
    out, _ = paged_read(pool, PagedView(table, ps))
    np.testing.assert_array_equal(np.asarray(out[0, ps:]),
                                  np.full((ps, 2), 7.0))
    # zero-filled trash -> unallocated span gathers exact zeros
    out0, _ = paged_read(pool.at[:ps].set(0.0), PagedView(table, ps))
    assert not np.any(np.asarray(out0[0, ps:]))


def test_paged_append_trash_sink_does_not_leak():
    """A retired/idle slot's table row is all zeros: its writes land in
    the trash page (page 0) and NO allocated page changes."""
    ps = 4
    pool = jnp.arange(3 * ps * 2, dtype=jnp.float32).reshape(3 * ps, 2)
    table = jnp.asarray([[0, 0]], jnp.int32)           # trash-routed slot
    new = jnp.full((1, 2, 2), -5.0)
    pos = jnp.asarray([[5, 6]], jnp.int32)             # page 1 of the slot
    out = _paged_append(pool, PagedView(table, ps), pos, new)
    np.testing.assert_array_equal(np.asarray(out[ps:]),
                                  np.asarray(pool[ps:]))
    assert np.any(np.asarray(out[:ps]) != np.asarray(pool[:ps]))


# --------------------------------------------------------------------------
# the stacked pool: each scanned layer writes and reads its own rows
# --------------------------------------------------------------------------

# smoke configs deep enough that the layer scan runs three times
# (deepseek-v3: one dense prefix layer + 3; jamba: 3 x mamba/attn)
STACK_LAYERS = {"qwen3-1.7b": 3, "deepseek-v3-671b": 4,
                "jamba-v0.1-52b": 6}


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("arch", sorted(STACK_LAYERS))
def test_paged_step_writes_each_layer_in_place(arch, kernel):
    """One paged decode step against the slab decode of the same
    history: each layer's pool rows change exactly at the written
    tokens, to that layer's new K/V (the slab's), and nowhere else —
    unallocated pages and the trash page included — and the logits are
    the slab's."""
    cfg = _cfg(arch, num_layers=STACK_LAYERS[arch], decode_kernel=kernel)
    assert cfg.block_structure()[2] == 3
    params = init_model(cfg, KEY)
    B, ps, W, P = 2, 8, 4, 13                   # write at position 13
    table = np.asarray([[6, 2, 0, 0], [3, 8, 0, 0]], np.int32)
    n_pages = 9
    slab = init_cache(cfg, B, W * ps, jnp.float32)
    pool = init_cache(cfg, B, W * ps, jnp.float32, pool=(n_pages, ps))
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
    slab = jax.tree_util.tree_map(
        lambda x: jax.random.normal(next(keys), x.shape, x.dtype), slab)

    def fill(path, p, s):
        """The slab's history at the table's pages; random elsewhere."""
        if p.shape == s.shape:                 # per-slot (SSM) state
            return s
        out = np.array(jax.random.normal(next(keys), p.shape, p.dtype))
        stacked = jax.tree_util.keystr(path).startswith("['blocks']")
        o, s = (out, np.asarray(s)) if stacked else (out[None],
                                                     np.asarray(s)[None])
        for b in range(B):
            for w in range(2):              # the pages holding 0..P
                o[:, table[b, w] * ps:(table[b, w] + 1) * ps] = \
                    s[:, b, w * ps:(w + 1) * ps]
        return jnp.asarray(out)
    pool = jax.tree_util.tree_map_with_path(fill, pool, slab)

    tok = jnp.asarray([[5], [9]], jnp.int32)
    want = apply_model(cfg, params, {"tokens": tok}, mode="decode",
                       cache=slab, cache_pos=P)
    got = apply_model(cfg, params, {"tokens": tok}, mode="decode",
                      cache=pool, cache_pos=jnp.full((B,), P, jnp.int32),
                      paged=PagedView(jnp.asarray(table), ps))
    written = [table[b, P // ps] * ps + P % ps for b in range(B)]
    for layer in ("prefix", "blocks"):
        for name in pool[layer]:
            for old, new, ref in zip(
                    *(jax.tree_util.tree_leaves(t[layer][name])
                      for t in (pool, got["cache"], want["cache"]))):
                old, new, ref = map(np.asarray, (old, new, ref))
                if old.shape == ref.shape:     # per-slot: the slab's
                    np.testing.assert_allclose(new, ref, rtol=1e-5,
                                               atol=1e-5)
                    continue
                if layer == "prefix":          # one layer, unstacked
                    old, new, ref = old[None], new[None], ref[None]
                for l in range(old.shape[0]):
                    keep = np.ones(old.shape[1], bool)
                    keep[written] = False
                    np.testing.assert_array_equal(
                        new[l, keep], old[l, keep],
                        err_msg=f"{layer}/{name} layer {l}: unwritten rows")
                    # rope at (B, S) positions rounds apart from the
                    # slab's (S,) ones in the last bits; another layer's
                    # entries would differ by O(1)
                    np.testing.assert_allclose(
                        new[l, written], ref[l, :, P], rtol=1e-5,
                        atol=1e-5,
                        err_msg=f"{layer}/{name} layer {l}: new entries")
    np.testing.assert_allclose(np.asarray(got["logits"]),
                               np.asarray(want["logits"]),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# greedy equality: pallas == xla through the engine (host)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-moe-16b",
                                  "deepseek-v3-671b"])
def test_host_engine_pallas_matches_xla(arch):
    cfg = _cfg(arch)
    params = init_model(cfg, jax.random.PRNGKey(3))
    prompts = _prompts(cfg, (7, 12, 5, 9), seed=10)
    ref = make_engine(cfg, params, engine="continuous", batch_size=2,
                      max_len=64).generate(prompts, 8)
    got = make_engine(cfg.with_overrides(decode_kernel="pallas"), params,
                      engine="continuous", batch_size=2,
                      max_len=64).generate(prompts, 8)
    for i, (r, g) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(r, g, err_msg=f"request {i}")


MESH_PALLAS_SNIPPET = """
import jax, numpy as np
from repro.configs import smoke_config
from repro.models import init_model
from repro.launch.mesh import make_serve_mesh
from repro.serve import make_engine

cfg = smoke_config({arch!r}).with_overrides(dtype="float32")
params = init_model(cfg, jax.random.PRNGKey(3))
prompts = [np.asarray(jax.random.randint(
    jax.random.PRNGKey(10 + i), (L,), 0, cfg.vocab_size))
    for i, L in enumerate((7, 12, 5, 9))]
ref = make_engine(cfg, params, engine="continuous", batch_size=2,
                  max_len=64).generate(prompts, 8)
eng = make_engine(cfg.with_overrides(decode_kernel="pallas"), params,
                  engine="continuous", batch_size=2, max_len=64,
                  mesh=make_serve_mesh(2, 4))
got = eng.generate(prompts, 8)
for i, (r, g) in enumerate(zip(ref, got)):
    assert np.array_equal(r, g), (i, r, g)
# kernel path must not cost pool distribution: storage stays sharded
per = eng.kv.pool_bytes_by_device()
tot = eng.kv.pool_bytes()
assert len(per) == 8 and max(per.values()) == tot // 4, (per, tot)
assert sum(per.values()) == 2 * tot
print("OK", {arch!r})
"""


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-moe-16b"])
def test_mesh_engine_pallas_matches_host_xla(arch):
    """decode_kernel="pallas" on the (2, 4) serve mesh: greedy outputs
    equal the host XLA reference engine, with the paged pool still
    genuinely model-sharded (the kernel pins its OPERANDS replicated,
    never the pool storage)."""
    out = run_with_devices(MESH_PALLAS_SNIPPET.format(arch=arch))
    assert "OK" in out


MESH_PALLAS_GATHER_SNIPPET = """
import re, jax, numpy as np
from repro.configs import smoke_config
from repro.models import init_model
from repro.launch.mesh import make_serve_mesh
from repro.serve import make_engine

cfg = smoke_config({arch!r}).with_overrides(dtype="float32",
                                            decode_kernel="pallas")
eng = make_engine(cfg, init_model(cfg, jax.random.PRNGKey(3)),
                  engine="continuous", batch_size=2, max_len=64,
                  mesh=make_serve_mesh(2, 4))
stacked = jax.tree_util.tree_leaves(eng.kv.cache["blocks"])
n_rep = stacked[0].shape[0]
assert n_rep >= 2, n_rep
gathered = []
for line in eng.lower_decode().compile().as_text().splitlines():
    if re.search(r" all-gather(-start)?\\(", line) and "attn.kv_read" in line:
        kind = line.split(" = ", 1)[1].split(" all-gather")[0]
        gathered += [int(np.prod([int(d) for d in dims.split(",") if d]))
                     for dims in re.findall(r"\\[([\\d,]*)\\]", kind)]
assert gathered, "no all-gather: the kernel operands are not replicated"
assert max(gathered) <= max(x.size // n_rep for x in stacked), gathered
print("OK", {arch!r})
"""


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v3-671b"])
def test_mesh_pallas_gathers_one_layer_of_the_pool(arch):
    """The kernel's operands are replicated on the serve mesh, so each
    layer all-gathers what it hands the kernel (``attn.kv_read``): one
    layer's rows of the scanned block's stacked pool, never the whole
    stack."""
    out = run_with_devices(MESH_PALLAS_GATHER_SNIPPET.format(arch=arch))
    assert "OK" in out


# --------------------------------------------------------------------------
# fused-sampling dispatch discipline
# --------------------------------------------------------------------------

def test_prefill_fused_sampling_dispatch_discipline():
    """Per request: ceil(S/prefill_chunk) prefill dispatches and ONE
    prefill host sync — the first-token sample rides the last chunk's
    compiled call, no separate sampling launch.  Decode: one dispatch
    and one sync per fused chunk."""
    cfg = _cfg()
    params = init_model(cfg, KEY)
    C, K, new = 8, 8, 17
    sch = ContinuousScheduler(cfg, params, slots=4, max_len=64,
                              page_size=16, prefill_chunk=C,
                              decode_chunk=K)
    lengths = (7, 12, 5, 9)
    sch.generate(_prompts(cfg, lengths), new)
    st = sch.stats()
    want_prefill = sum(-(-L // C) for L in lengths)
    assert st["prefill_dispatches"] == want_prefill, st
    assert st["prefill_host_syncs"] == len(lengths), st
    assert st["decode_dispatches"] == st["decode_host_syncs"], st
    # fused loop: K tokens per decode sync; all slots run lockstep here
    assert st["decode_host_syncs"] == -(-(new - 1) // K), st
    assert st["dispatches"] == (st["prefill_dispatches"]
                                + st["decode_dispatches"]), st
    assert st["host_syncs"] == (st["prefill_host_syncs"]
                                + st["decode_host_syncs"]), st
    assert st["syncs_per_token"] < 0.25, st


# --------------------------------------------------------------------------
# perf-model calibration + microbench row schema
# --------------------------------------------------------------------------

ROWS = [
    {"arch": "a", "phase": "ar_step", "batch": 4, "tokens": 8,
     "time_s": 0.08, "flags": "baseline"},
    {"arch": "a", "phase": "ar_step", "batch": 4, "tokens": 8,
     "time_s": 0.064, "flags": "tuned"},
    {"arch": "a", "phase": "prefill", "batch": 4, "tokens": 1,
     "time_s": 0.002, "flags": "baseline"},
    {"arch": "b", "phase": "ar_step", "batch": 2, "tokens": 8,
     "time_s": 0.4, "flags": "baseline"},
]


def test_calibrate_kernel_time_selects_best_row():
    # fastest matching ar_step row, divided down to per-token
    assert perf_model.calibrate_kernel_time(ROWS, arch="a") \
        == pytest.approx(0.064 / 8)
    assert perf_model.calibrate_kernel_time(ROWS, arch="a",
                                            per_token=False) \
        == pytest.approx(0.064)
    assert perf_model.calibrate_kernel_time(ROWS, arch="b", batch=2) \
        == pytest.approx(0.05)
    with pytest.raises(ValueError):
        perf_model.calibrate_kernel_time(ROWS, arch="a", batch=16)


def test_decode_step_time_kernel_floor():
    base = perf_model.decode_step_time(1e9, 1e6, batch=8)
    assert perf_model.decode_step_time(1e9, 1e6, batch=8,
                                       kernel_time_s=0.0) == base
    # a measured floor above the roofline wins
    assert perf_model.decode_step_time(
        1e9, 1e6, batch=8, kernel_time_s=base * 10) == base * 10
    # and feeds through to throughput
    slow = perf_model.decode_tokens_per_s(1e9, 1e6, batch=8,
                                          kernel_time_s=base * 10)
    assert slow == pytest.approx(8 / (base * 10))


def test_microbench_rows_schema():
    """One in-process sweep cell produces rows with the schema the
    calibration helper and the CI artifact consumers read."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "benchmarks"))
    import decode_microbench as mb
    rows = mb._bench_arch("qwen3-1.7b", "schema-test", repeats=1,
                          quick=True)
    phases = {r["phase"] for r in rows}
    assert phases == {"prefill", "insert", "ar_step"}
    kernels = {r["decode_kernel"] for r in rows}
    assert kernels == {"xla", "pallas"}
    for r in rows:
        for k in ("arch", "phase", "decode_kernel", "batch", "page_size",
                  "block_q", "block_kv", "flags", "tokens", "time_s"):
            assert k in r, (k, r)
        assert r["time_s"] > 0
        assert r["tokens"] == (mb.DECODE_CHUNK
                               if r["phase"] == "ar_step" else 1)
    # rows are calibration-ready
    assert perf_model.calibrate_kernel_time(rows, arch="qwen3-1.7b") > 0
