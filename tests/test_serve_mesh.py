"""Mesh-sharded serving: the continuous engine on the production
topology (data x model serve mesh) must be a pure placement change —
greedy outputs bitwise-equal to the host-mesh engine, with the paged
pool genuinely distributed (no device holds the full pool).

Every mesh test runs under ``run_with_devices`` (a subprocess with
``--xla_force_host_platform_device_count=8``): a (2, 4) serve mesh —
2 DP replica groups, 4-way model-sharded decode — the host-scale
instance of the production 16x16 layout.
"""
import numpy as np
import pytest

from conftest import run_with_devices

# the two acceptance archs: GQA (qwen3: kv_heads=2 < model=4 exercises
# the head_dim-sharding fallback) and MoE (deepseek: expert-parallel
# decode dispatch + head-sharded pool)
MESH_BITWISE_SNIPPET = """
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

solo = make_engine(cfg, params, engine="continuous", batch_size=2,
                   max_len=64)
ref = solo.generate(prompts, 8)

mesh = make_serve_mesh(2, 4)
eng = make_engine(cfg, params, engine="continuous", batch_size=2,
                  max_len=64, mesh=mesh)
got = eng.generate(prompts, 8)
for i, (r, g) in enumerate(zip(ref, got)):
    assert np.array_equal(r, g), (i, r, g)

# ---- live-buffer sweep: the pool is genuinely distributed ----
per = eng.kv.pool_bytes_by_device()
tot = eng.kv.pool_bytes()
assert len(per) == 8, per                       # every device holds a shard
assert all(b < tot for b in per.values()), \\
    "a device holds the full pool"
# feature axes shard 4-way over "model": per-device == pool/model_size
assert max(per.values()) == tot // 4, (per, tot)
assert sum(per.values()) == 2 * tot             # 2 data-replicas of the pool
assert eng.kv.pool_bytes_per_device() == tot // 4
print("OK", {arch!r})
"""


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-moe-16b"])
def test_mesh_continuous_bitwise_and_pool_distributed(arch):
    out = run_with_devices(MESH_BITWISE_SNIPPET.format(arch=arch))
    assert "OK" in out


PREFIX_MESH_SNIPPET = """
import jax, numpy as np
from repro.configs import smoke_config
from repro.models import init_model
from repro.launch.mesh import make_serve_mesh
from repro.serve import make_engine

cfg = smoke_config({arch!r}).with_overrides(dtype="float32")
params = init_model(cfg, jax.random.PRNGKey(3))
shared = np.asarray(jax.random.randint(
    jax.random.PRNGKey(9), (16,), 0, cfg.vocab_size))
rng = np.random.default_rng(3)
prompts = [np.concatenate([shared,
                           rng.integers(0, cfg.vocab_size, 3 + i)
                           .astype(np.int32)]) for i in range(3)]
prompts.append(prompts[0].copy())     # exact repeat: the COW-fork path

kw = dict(engine="continuous", batch_size=2, max_len=64, page_size=8,
          prefill_chunk=8, decode_chunk=4, num_pages=40)
ref = make_engine(cfg, params, **kw).generate(prompts, 6)

mesh = make_serve_mesh(2, 4)
eng = make_engine(cfg, params, prefix_cache=True, mesh=mesh, **kw)
got = eng.generate(prompts, 6)
for i, (r, g) in enumerate(zip(ref, got)):
    assert np.array_equal(r, g), (i, r, g)
st = eng.stats()
assert st["prefix_hit_rate"] > 0, st
# aliasing is host-table-only: the pool stays genuinely distributed
per = eng.kv.pool_bytes_by_device()
assert len(per) == 8 and max(per.values()) == eng.kv.pool_bytes() // 4
print("OK", {arch!r})
"""


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-moe-16b"])
def test_mesh_prefix_cache_bitwise(arch):
    """Radix prefix cache on the (2, 4) serve mesh: aliasing edits only
    the replicated HOST page table while pool feature axes stay
    model-sharded — cache on vs off (and vs host) must be bitwise."""
    out = run_with_devices(PREFIX_MESH_SNIPPET.format(arch=arch))
    assert "OK" in out


def test_mesh_legacy_engine_matches_solo():
    """The slab reference engine takes the same mesh= and must also be
    placement-invariant."""
    run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_config
    from repro.models import init_model
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import make_engine

    cfg = smoke_config("qwen3-1.7b").with_overrides(dtype="float32")
    params = init_model(cfg, jax.random.PRNGKey(0))
    pr = jnp.asarray(np.tile(np.arange(4, 12, dtype=np.int32), (2, 1)))
    ref = np.asarray(make_engine(cfg, params, engine="legacy",
                                 batch_size=2, max_len=64,
                                 dtype=jnp.float32).generate(pr, 8))
    eng = make_engine(cfg, params, engine="legacy", batch_size=2,
                      max_len=64, dtype=jnp.float32,
                      mesh=make_serve_mesh(2, 4))
    got = np.asarray(eng.generate(pr, 8))
    assert np.array_equal(ref, got), (ref, got)
    """)


def test_pool_specs_follow_divisibility():
    """pool_spec unit semantics on a real (2, 4) mesh: kv heads shard
    over "model" when divisible, fall back to head_dim, replicate
    per-slot leaves; MLA latent shards its last axis."""
    run_with_devices("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import smoke_config
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import PagedKVCache
    from repro.sharding import pool_spec

    mesh = make_serve_mesh(2, 4)

    class Leaf:
        def __init__(self, shape): self.shape = shape

    cfg = smoke_config("deepseek-moe-16b")   # kv_heads=4 : head-sharded
    assert pool_spec(cfg, mesh, "/blocks/k", Leaf((1, 256, 4, 64)),
                     -1) == P(None, None, "model", None)
    cfg = smoke_config("qwen3-1.7b")         # kv_heads=2 : head_dim
    assert pool_spec(cfg, mesh, "/layers/0/k", Leaf((256, 2, 64)),
                     -1) == P(None, None, "model")
    # MLA latent (N, r): last axis over "model"
    v3 = smoke_config("deepseek-v3-671b")
    assert pool_spec(v3, mesh, "/layers/0/ckv", Leaf((256, 32)),
                     -1) == P(None, "model")
    # per-slot (SSM) leaves: replicated whatever their shape
    assert pool_spec(cfg, mesh, "/layers/1/ssm", Leaf((4, 8, 16)),
                     0) == P(None, None, None)
    print("OK")
    """)


def test_kvcache_accounting_host_path():
    """Host path (mesh=None): the per-device sweep degenerates to the
    full pool on the single default device."""
    import jax.numpy as jnp

    from repro.configs import smoke_config
    from repro.serve import PagedKVCache

    cfg = smoke_config("qwen3-1.7b").with_overrides(dtype="float32")
    kv = PagedKVCache(cfg, slots=2, max_len=64, page_size=16,
                      dtype=jnp.float32)
    assert kv.shardings is None
    assert kv.pool_bytes_per_device() == kv.pool_bytes()


def test_launcher_mesh_end_to_end_no_systemexit():
    """The acceptance path: the launcher runs the CONTINUOUS engine on
    a serve mesh (no --reduced refusal, no SystemExit) and its outputs
    equal the one-device run bit-for-bit."""
    run_with_devices("""
    from repro.launch.serve import main

    base = ["--arch", "deepseek-moe-16b", "--reduced", "--batch", "2",
            "--prompt-len", "8", "--new-tokens", "6",
            "--engine", "continuous"]
    host = main(base + ["--mesh-shape", "1x1"])   # one device
    mesh = main(base + ["--mesh-shape", "2x4"])
    assert host == mesh, (host, mesh)
    print("OK")
    """)


def test_launcher_requests_normalisation_and_legacy_refusal():
    """S1: --requests 0 / omitted both resolve to --batch in one place;
    the legacy-engine refusal reports the RESOLVED values."""
    from repro.launch.serve import main

    base = ["--arch", "qwen3-1.7b", "--reduced", "--batch", "2",
            "--prompt-len", "6", "--new-tokens", "4"]
    outs_default = main(base)
    assert len(outs_default) == 2                 # resolved to --batch
    outs_zero = main(base + ["--requests", "0"])  # legacy sentinel
    assert outs_zero == outs_default

    with pytest.raises(SystemExit) as ei:
        main(base + ["--engine", "legacy", "--requests", "5"])
    msg = str(ei.value)
    assert "--requests 5" in msg and "--batch 2" in msg

    # the sentinel must NOT trip the refusal (0 means "--batch", not 0)
    outs = main(base + ["--engine", "legacy", "--requests", "0"])
    assert len(np.asarray(outs)) == 2


def test_mesh_output_projection_rounds_once():
    """An output projection whose contracting axis the serve mesh splits
    over "model" sums float32 partial products and rounds once, so in
    bfloat16 it equals one device's bit for bit (rounding each device's
    partial product first put 40% of these elements an ulp apart, and
    flipped a greedy token in the launcher test above)."""
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_serve_mesh
    from repro.models.layers import out_proj
    from repro.sharding import ctx as shctx

    mesh = make_serve_mesh(2, 4)
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (4, 8, 512), jnp.bfloat16)
    w = (jax.random.normal(kw, (512, 256)) * 0.05).astype(jnp.bfloat16)
    one = np.asarray(jax.jit(out_proj)(x, w), np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(None, None, "model")))
    ws = jax.device_put(w, NamedSharding(mesh, P("model", None)))
    with shctx.serve_topology(shctx.ServeTopology.from_mesh(mesh)):
        got = jax.jit(out_proj)(xs, ws)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32), one)
    print("OK")
    """)
    assert "OK" in out


def test_mesh_moe_combine_rounds_once():
    """The expert-parallel MoE layer in bfloat16 gives the same bits on
    a (2, 4) serve mesh as on a one-device one, with the top-k slots of
    a token spread over ranks (8 experts, top 4: two per rank): the
    serving combine sums the slots and the ranks in float32 and rounds
    once."""
    out = run_with_devices("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import moe
    from repro.sharding import ctx as shctx

    cfg = smoke_config("deepseek-moe-16b")
    cfg = cfg.with_overrides(moe=dataclasses.replace(
        cfg.moe, num_experts=8, top_k=4))
    p = moe.init_moe(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model),
                          jnp.bfloat16)
    outs = []
    for shape in ((1, 1), (2, 4)):
        topo = shctx.ServeTopology.from_mesh(make_serve_mesh(*shape))
        with shctx.serve_topology(topo):
            y = jax.jit(lambda p, x: moe.apply_moe(cfg, p, x)[0])(p, x)
        assert y.dtype == jnp.bfloat16
        outs.append(np.asarray(y, np.float32))
    np.testing.assert_array_equal(outs[1], outs[0])
    print("OK")
    """)
    assert "OK" in out
