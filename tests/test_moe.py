"""MoE: routing invariants, capacity semantics, EP-vs-dense equality."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro.configs import smoke_config
from repro.models import moe as moe_lib

KEY = jax.random.PRNGKey(3)


def test_router_topk_weights_normalised():
    """A renormalising softmax router (``norm_topk_prob``, the default;
    DeepSeekMoE itself does not renormalise) gives gates summing to 1."""
    cfg = smoke_config("deepseek-moe-16b")
    cfg = cfg.with_overrides(moe=dataclasses.replace(cfg.moe,
                                                     norm_topk_prob=True))
    p = moe_lib.init_moe(cfg, KEY)
    xf = jax.random.normal(KEY, (32, cfg.d_model))
    w, idx, aux = moe_lib._routing(cfg, {"router": p["router"]}, xf)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    assert int(idx.max()) < cfg.moe.num_experts
    assert float(aux) > 0.0


def test_generous_capacity_means_no_drops():
    """With capacity >= N*k the MoE output equals the uncapped weighted
    sum of expert outputs."""
    cfg = smoke_config("jamba-v0.1-52b")
    p = moe_lib.init_moe(cfg, KEY)
    x = jax.random.normal(KEY, (2, 8, cfg.d_model))
    y_hi, _ = moe_lib.apply_moe_dense(cfg, p, x, capacity_factor=64.0)

    # brute-force: every expert on every token, weighted by router
    m = cfg.moe
    xf = x.reshape(-1, cfg.d_model)
    w, idx, _ = moe_lib._routing(cfg, {"router": p["router"]}, xf)
    dense = jnp.stack([
        moe_lib._expert_ffn(cfg, jax.tree_util.tree_map(
            lambda t: t[e:e + 1], p["experts"]),
            xf[None])[0]
        for e in range(m.num_experts)])               # (E, N, d)
    want = jnp.zeros_like(xf)
    for j in range(m.top_k):
        want = want + w[:, j:j + 1] * dense[idx[:, j], jnp.arange(xf.shape[0])]
    want = want.reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y_hi), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def _moe_cfg(arch, norm):
    cfg = smoke_config(arch)
    if norm is not None:
        cfg = cfg.with_overrides(moe=dataclasses.replace(
            cfg.moe, norm_topk_prob=norm))
    return cfg


@pytest.mark.parametrize("arch,norm", [
    ("deepseek-moe-16b", None),         # as published: not renormalised
    ("deepseek-moe-16b", True),
    ("jamba-v0.1-52b", None),           # as published: not renormalised
], ids=["deepseek-moe-published", "deepseek-moe-renormalised", "jamba"])
def test_routed_part_is_the_gate_weighted_sum(arch, norm):
    """The softmax router's gates are the top-k softmax scores over all
    experts, divided by their sum only where ``norm_topk_prob`` says so
    (bit for bit the formula), and the layer's routed part is the
    gate-weighted sum of the chosen experts' FFNs."""
    cfg = _moe_cfg(arch, norm)
    m = cfg.moe
    assert m.norm_topk_prob == (norm if norm is not None else False)
    p = moe_lib.init_moe(cfg, KEY)
    x = jax.random.normal(KEY, (2, 8, cfg.d_model))
    xf = x.reshape(-1, cfg.d_model)

    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    w, got_idx, _ = moe_lib._routing(cfg, p, xf)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(top))

    y, _ = moe_lib.apply_moe_dense(cfg, p, x, capacity_factor=64.0)
    if m.num_shared_experts:
        y = y - moe_lib.apply_mlp(p["shared"], x, gated=cfg.mlp_gated)
    want = jnp.zeros_like(xf)
    for e in range(m.num_experts):
        ffn = moe_lib._expert_ffn(cfg, jax.tree_util.tree_map(
            lambda t: t[e:e + 1], p["experts"]), xf[None])[0]
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)
        want = want + gate[:, None] * ffn
    np.testing.assert_allclose(np.asarray(y).reshape(xf.shape),
                               np.asarray(want), atol=1e-4, rtol=1e-4)


def test_sigmoid_router_weights_are_renormalised_scores():
    """DeepSeek-V3's sigmoid router, as before: experts chosen by score
    plus the balance bias, weighted by the bias-free scores renormalised
    over the chosen k."""
    cfg = smoke_config("deepseek-v3-671b")
    p = moe_lib.init_moe(cfg, KEY)
    p = dict(p, router_bias=jax.random.normal(
        jax.random.PRNGKey(5), p["router_bias"].shape))
    xf = jax.random.normal(KEY, (32, cfg.d_model))
    scores = jax.nn.sigmoid(xf @ p["router"])
    _, idx = jax.lax.top_k(scores + p["router_bias"][None], cfg.moe.top_k)
    top = jnp.take_along_axis(scores, idx, axis=1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    w, got_idx, _ = moe_lib._routing(cfg, p, xf)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(top))


def test_tight_capacity_drops_tokens():
    cfg = smoke_config("deepseek-moe-16b")
    p = moe_lib.init_moe(cfg, KEY)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    y_tight, _ = moe_lib.apply_moe_dense(cfg, p, x, capacity_factor=0.25)
    y_loose, _ = moe_lib.apply_moe_dense(cfg, p, x, capacity_factor=64.0)
    assert np.abs(np.asarray(y_tight) - np.asarray(y_loose)).max() > 1e-4


EP_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.compat import make_mesh, auto_axis_types, set_mesh
from repro.configs import smoke_config
from repro.models import moe as moe_lib
from repro.sharding.ctx import set_activation_mesh
key = jax.random.PRNGKey(0)
mesh = make_mesh({mesh_shape}, {mesh_axes},
                 axis_types=auto_axis_types({ndim}))
cfg = smoke_config('deepseek-moe-16b')
{cfg_override}
p = moe_lib.init_moe(cfg, key)
x = jax.random.normal(key, {x_shape}, jnp.float32)
set_activation_mesh(None)
y0, a0 = jax.jit(lambda p, x: moe_lib.apply_moe(cfg, p, x,
                 capacity_factor=8.0))(p, x)
set_activation_mesh(mesh)
with set_mesh(mesh):
    y1, a1 = jax.jit(lambda p, x: moe_lib.apply_moe(cfg, p, x,
                     capacity_factor=8.0))(p, x)
set_activation_mesh(None)
err = float(jnp.abs(y0 - y1).max())
print('ERR', err)
assert err < 5e-5, err
"""


def test_ep_all_to_all_path_matches_dense():
    run_with_devices(EP_SNIPPET.format(
        mesh_shape="(2, 2)", mesh_axes="('data', 'model')", ndim=2,
        cfg_override="", x_shape="(4, 8, cfg.d_model)"))


def test_ep_expert_fsdp_path_matches_dense():
    run_with_devices(EP_SNIPPET.format(
        mesh_shape="(2, 2)", mesh_axes="('data', 'model')", ndim=2,
        cfg_override=("cfg = cfg.with_overrides(moe=dataclasses.replace("
                      "cfg.moe, num_experts=6, top_k=2, d_expert=128))"),
        x_shape="(4, 8, cfg.d_model)"))


def test_ep_unsharded_batch_matches_dense():
    run_with_devices(EP_SNIPPET.format(
        mesh_shape="(2, 2)", mesh_axes="('data', 'model')", ndim=2,
        cfg_override="", x_shape="(1, 8, cfg.d_model)"))


def test_ep_multipod_matches_dense():
    run_with_devices(EP_SNIPPET.format(
        mesh_shape="(2, 2, 2)", mesh_axes="('pod', 'data', 'model')",
        ndim=3, cfg_override="", x_shape="(4, 8, cfg.d_model)"))


# --------------------------------------------------------------------------
# aux-free router-bias balancing (V3): dtype-stable update
# --------------------------------------------------------------------------

def test_update_router_bias_exact_gamma_opposite_directions():
    """Over/underloaded experts move by EXACTLY gamma in opposite
    directions — in fp32, regardless of the count dtype."""
    cfg = smoke_config("deepseek-v3-671b")   # sigmoid router: has bias
    p = moe_lib.init_moe(cfg, KEY)
    gamma = 1e-3
    E = cfg.moe.num_experts
    counts = np.full((E,), 8)
    counts[0], counts[1] = 20, 0          # over / under; rest at mean-ish
    for dt in (np.int32, np.float32, jnp.bfloat16):
        new = moe_lib.update_router_bias(cfg, p, jnp.asarray(counts, dt),
                                         gamma=gamma)
        d = np.asarray(new, np.float64) - np.asarray(p["router_bias"],
                                                     np.float64)
        assert d[0] == -np.float32(gamma), (dt, d[0])
        assert d[1] == +np.float32(gamma), (dt, d[1])


def test_update_router_bias_no_bf16_freeze():
    """The regression: a bf16-accumulated update at |bias|~8 rounds a
    1e-3 step to ZERO (ulp is 0.0625 there) and balancing silently
    freezes; the fp32 accumulate keeps stepping."""
    cfg = smoke_config("deepseek-v3-671b")
    p = moe_lib.init_moe(cfg, KEY)
    big = jnp.full_like(p["router_bias"], 8.0)
    p = dict(p, router_bias=big)
    counts = jnp.asarray(
        np.r_[20, np.full((cfg.moe.num_experts - 1,), 8)], jnp.bfloat16)
    new = moe_lib.update_router_bias(cfg, p, counts, gamma=1e-3)
    # the overloaded expert's bias must actually move (fp32 resolves it)
    assert float(new[0]) < 8.0
