"""Fine-grained mixture-of-experts (DeepSeekMoE / Jamba style).

Two dispatch implementations, numerically identical:

* ``apply_moe`` (no mesh): capacity-based scatter/gather in plain jnp —
  the reference path for CPU tests and small models.

* ``apply_moe`` (mesh registered): explicit expert-parallel shard_map.
  GSPMD cannot partition data-dependent scatter/gather across a sharded
  expert axis (it replicates — measured 98 GB/device on the 671B
  config), so the production path makes the communication explicit, the
  way TPU MoE systems actually run:

    - each data-shard routes its local tokens and packs them into a
      local (E, C_loc, d) buffer (dense local scatter);
    - if experts are sharded over "data" (256-expert configs), a
      ``lax.all_to_all`` over the data axis exchanges expert rows —
      THE MoE collective the roofline measures;
    - each model-rank slices its own expert rows (activations are
      replicated over "model", so no collective is needed there);
    - expert FFNs run as dense batched matmuls on local shards;
    - the combine retraces the path and finishes with a psum over
      "model" (which merges with the layer's tensor-parallel reduce);
      serving sums in float32 and rounds once, as one device does.

Router: softmax over experts and the top-k, weighted by their softmax
scores, renormalised over the k where ``MoEConfig.norm_topk_prob`` says
so (DeepSeekMoE does not), or DeepSeek-V3's sigmoid scores renormalised
over the k; plus the Switch-style load-balance auxiliary loss
(coefficient in MoEConfig).  Shared experts (DeepSeek) run densely on
every token outside shard_map.

Both paths name their parts with ``jax.named_scope`` under the layer's
``moe``, so a device trace splits the layer: ``moe.route`` (router
logits, scores, top-k), ``moe.dispatch`` (each slot's position in its
expert, the scatter into the ``(E, C, d)`` buffers, the forward
all-to-all), ``moe.experts`` (the batched expert FFN), ``moe.combine``
(the gather back, the gate weighting, the reverse exchange and sums)
and ``moe.shared``.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map, shard_map_kwargs

from repro.models.layers import dense_init, init_mlp, apply_mlp, scoped
from repro.sharding import ctx as shctx
from repro.sharding.ctx import constrain_ecd, constrain_tokens

CAPACITY_FACTOR = 1.25


def init_moe(cfg, key):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"router": dense_init(ks[0], d, m.num_experts, std=0.02)}
    if getattr(m, "router_type", "softmax") == "sigmoid":
        # V3 aux-free balancing bias: used for SELECTION only, excluded
        # from gradients (updated by the trainer from load statistics)
        p["router_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    # routed experts: stacked (E, ...) for batched einsum
    ke = jax.random.split(ks[1], 3)
    mats = {"w_up": dense_init(ke[0], d, m.num_experts * m.d_expert
                               ).reshape(d, m.num_experts, m.d_expert
                                         ).transpose(1, 0, 2),
            "w_down": dense_init(ke[1], m.d_expert,
                                 m.num_experts * d
                                 ).reshape(m.d_expert, m.num_experts, d
                                           ).transpose(1, 0, 2)}
    if cfg.mlp_gated:
        mats["w_gate"] = dense_init(ke[2], d, m.num_experts * m.d_expert
                                    ).reshape(d, m.num_experts, m.d_expert
                                              ).transpose(1, 0, 2)
    p["experts"] = mats
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks[2], d, m.num_shared_experts * m.d_expert,
                               gated=cfg.mlp_gated)
    return p


@scoped("moe.route")
def _routing(cfg, p, xf):
    """xf: (N, d) -> (top-k weights (N,k), top-k idx (N,k), aux loss)."""
    m = cfg.moe
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    if getattr(m, "router_type", "softmax") == "sigmoid":
        # DeepSeek-V3: sigmoid affinity; SELECT by score + balance bias
        # (bias carries no gradient and no weight), weight by the
        # bias-free scores renormalised over the selection.
        scores = jax.nn.sigmoid(logits)                        # (N, E)
        bias = jax.lax.stop_gradient(p["router_bias"])
        _, top_idx = jax.lax.top_k(scores + bias[None, :], m.top_k)
        top_w = jnp.take_along_axis(scores, top_idx, axis=1)
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
        probs = scores / (jnp.sum(scores, axis=-1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)                # (N, E)
        top_w, top_idx = jax.lax.top_k(probs, m.top_k)         # (N, k)
        if m.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # Switch aux loss: E * sum_e f_e * P_e (kept tiny for sigmoid mode —
    # V3 relies on the bias, the aux term is a sequence-level backstop)
    one_hot = jax.nn.one_hot(top_idx, m.num_experts, dtype=jnp.float32)
    f = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)             # fraction routed
    P = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(f * P) * m.router_aux_coef
    return top_w, top_idx, aux


def update_router_bias(cfg, p, counts, *, gamma=1e-3):
    """V3 aux-free balancing: bias += gamma (underloaded experts),
    -= gamma (overloaded).  counts: (E,) tokens routed per expert this
    step (host-side trainer utility, outside the gradient path).

    The update accumulates in fp32 regardless of the bias/count dtypes:
    a bf16 bias near +/-8 cannot resolve a 1e-3 step (ulp there is
    0.0625), so low-precision accumulation silently freezes the
    balancing long before the bias saturates; integer counts would
    also truncate the mean."""
    bias = p["router_bias"]
    counts = jnp.asarray(counts, jnp.float32)
    mean = jnp.mean(counts)
    step = jnp.float32(gamma) * jnp.sign(mean - counts)
    return (bias.astype(jnp.float32) + step).astype(bias.dtype)


def apply_moe(cfg, p, x, *, capacity_factor=None):
    """x: (B, S, d) -> (y, aux_loss).  Dispatches on mesh presence."""
    if capacity_factor is None:
        capacity_factor = getattr(cfg.moe, "capacity_factor",
                                  CAPACITY_FACTOR)
    if shctx.get_activation_mesh() is not None:
        return apply_moe_ep(cfg, p, x, capacity_factor=capacity_factor)
    return apply_moe_dense(cfg, p, x, capacity_factor=capacity_factor)


def apply_moe_dense(cfg, p, x, *, capacity_factor=CAPACITY_FACTOR):
    """Reference scatter/gather path (single device / tests)."""
    m = cfg.moe
    B, S, d = x.shape
    N = B * S
    xf = x.reshape(N, d)

    top_w, top_idx, aux = _routing(cfg, p, xf)
    k = m.top_k
    E = m.num_experts
    C = max(1, int(capacity_factor * N * k / E))
    # round capacity to a multiple of 8 lanes-friendly size
    C = min(N, -(-C // 8) * 8)
    flat_e, safe_pos, keep, flat_w = _slots(top_idx, top_w, C, E)
    buf = _scatter(xf, flat_e, safe_pos, keep, C, E, k)
    out_buf = _expert_ffn(cfg, p["experts"], buf, pin=constrain_ecd)
    y = _gather(out_buf, flat_e, safe_pos, flat_w, k).reshape(B, S, d)

    if m.num_shared_experts:
        y = y + _shared(cfg, p["shared"], x)
    return y, aux


@scoped("moe.dispatch")
def _slots(top_idx, top_w, C, E):
    """Each (token, slot)'s position within its expert, in token order:
    (flat_e, safe_pos, keep, flat_w) over the N*k slots; a slot past its
    expert's capacity C is dropped (keep False, weight 0)."""
    flat_e = top_idx.reshape(-1)                                # (N*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)         # (N*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                        # running count
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < C
    flat_w = top_w.reshape(-1) * keep
    safe_pos = jnp.where(keep, flat_pos, 0)
    return flat_e, safe_pos, keep, flat_w


@scoped("moe.dispatch")
def _scatter(xf, flat_e, safe_pos, keep, C, E, k):
    """Tokens into their experts' (E, C, d) capacity buffers."""
    N, d = xf.shape
    dt = xf.dtype
    tok_idx = jnp.repeat(jnp.arange(N), k)
    buf = jnp.zeros((E, C, d), dt)
    upd = constrain_tokens(xf[tok_idx] * keep[:, None].astype(dt))
    buf = buf.at[flat_e, safe_pos].add(upd, mode="drop")
    return constrain_ecd(buf)      # expert-parallel layout (the all-to-all)


@scoped("moe.combine")
def _gather(out_buf, flat_e, safe_pos, flat_w, k):
    """Expert outputs back to their tokens, weighted by the gates; the
    (N*k) slots are token-major, so a reshape-sum over the k slot axis
    recombines them.  Returns (N, d)."""
    dt = out_buf.dtype
    y = constrain_tokens(out_buf[flat_e, safe_pos]
                         * flat_w[:, None].astype(dt))
    return y.reshape(-1, k, y.shape[-1]).sum(axis=1)


@scoped("moe.shared")
def _shared(cfg, p, x):
    """The always-on shared experts: one dense MLP over every token."""
    return apply_mlp(p, x, gated=cfg.mlp_gated)


# ==========================================================================
# expert-parallel shard_map path (production mesh)
# ==========================================================================

def _ep_factors(cfg, mesh):
    """How the expert axis maps onto the mesh.

    Returns (ep_data, ep_model): E is sharded over `model` when E % model
    == 0, and additionally over `data` when E % (model*data) == 0 (the
    256-expert configs).  Otherwise experts stay model-sharded and their
    FFN dim is tensor-parallel over `data` (Megatron expert-TP)."""
    E = cfg.moe.num_experts
    msz = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    dsz = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
    ep_model = msz if (msz > 1 and E % msz == 0) else 1
    ep_data = dsz if (ep_model == msz and dsz > 1
                      and (E // ep_model) % dsz == 0) else 1
    return ep_data, ep_model


def _route_local(cfg, p, xf, capacity_factor):
    """Local routing: xf (N, d) -> (flat_e, safe_pos, keep, flat_w, aux, C).
    Pure-local (no collectives)."""
    m = cfg.moe
    N = xf.shape[0]
    k, E = m.top_k, m.num_experts
    top_w, top_idx, aux = _routing(cfg, p, xf)
    C = max(1, int(capacity_factor * N * k / E))
    C = min(max(N, 8), -(-C // 8) * 8)
    flat_e, safe_pos, keep, flat_w = _slots(top_idx, top_w, C, E)
    return flat_e, safe_pos, keep, flat_w, aux, C


@scoped("moe.dispatch")
def _pack(xf, flat_e, safe_pos, keep, C, rows, *, start=0, k=1):
    """Scatter tokens into expert rows [start, start+rows).  One scatter
    per top-k slot so the (N*k, d) token-copy tensor is never
    materialised (measured 13 GB/device at 262k tokens otherwise).
    Returns (buf (rows, C, d), sel mask over the N*k slots)."""
    N, d = xf.shape
    dt = xf.dtype
    sel = keep & (flat_e >= start) & (flat_e < start + rows)
    le = jnp.where(sel, flat_e - start, 0).reshape(N, k)
    pos = safe_pos.reshape(N, k)
    selk = sel.reshape(N, k)
    buf = jnp.zeros((rows, C, d), dt)
    for j in range(k):
        buf = buf.at[le[:, j], pos[:, j]].add(
            xf * selk[:, j][:, None].astype(dt))
    return buf, sel


@scoped("moe.combine")
def _combine(out, flat_e, safe_pos, flat_w, sel, k, dt, *, start=0,
             local_rows=None):
    """Gather expert outputs back per top-k slot and weight-sum them in
    ``dt``.  out: (rows, C, d) local expert outputs.  ``local_rows``
    overrides the expert-id -> local-row mapping (default: flat_e -
    start)."""
    N = flat_e.shape[0] // k
    rows = local_rows if local_rows is not None else flat_e - start
    le = jnp.where(sel, rows, 0).reshape(N, k)
    pos = safe_pos.reshape(N, k)
    w = (flat_w * sel).reshape(N, k)
    y = None
    for j in range(k):
        yj = out[le[:, j], pos[:, j]].astype(dt) * w[:, j][:, None].astype(dt)
        y = yj if y is None else y + yj
    return y


@scoped("moe.experts")
def _expert_ffn(cfg, experts, buf, pin=lambda t: t):
    """Dense batched FFN over a local expert buffer (E, C, d); ``pin``
    lays out its hidden and output buffers (the dense path pins them to
    the expert-parallel layout)."""
    dt = buf.dtype
    w_up = experts["w_up"].astype(dt)
    w_down = experts["w_down"].astype(dt)
    w_gate = experts.get("w_gate")
    up = jnp.einsum("ecd,edf->ecf", buf, w_up)
    if w_gate is not None:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                                   w_gate.astype(dt))) * up
    else:
        h = jax.nn.gelu(up)
    h = pin(h)
    return pin(jnp.einsum("ecf,efd->ecd", h, w_down))


def apply_moe_ep(cfg, p, x, *, capacity_factor=CAPACITY_FACTOR):
    """Expert-parallel MoE under the registered mesh (see module doc)."""
    mesh = shctx.get_activation_mesh()
    m = cfg.moe
    B, S, d = x.shape
    dt = x.dtype
    ep_data, ep_model = _ep_factors(cfg, mesh)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dsz = axis_sizes.get("data", 1)
    msz = axis_sizes.get("model", 1)
    has_pod = "pod" in mesh.axis_names
    dp_ax = ("pod", "data") if has_pod else ("data",)
    batch_sharded = B % (dsz * (axis_sizes.get("pod", 1))) == 0

    bspec = P(dp_ax if len(dp_ax) > 1 else dp_ax[0], None, None) \
        if batch_sharded else P(None, None, None)
    # router + experts enter with their parameter shardings
    from repro.sharding.rules import param_spec, ShardingConfig
    sh = ShardingConfig()
    especs = {kk: param_spec(cfg, mesh, f"ffn/experts/{kk}", vv, sh)
              for kk, vv in p["experts"].items()}
    rspec = P(None, None)

    # is the expert FFN dim tensor-parallel over 'data'? (expert-TP mode)
    f_tp = (ep_data == 1 and dsz > 1 and m.d_expert % dsz == 0
            and msz > 1 and m.num_experts % msz == 0)

    E = m.num_experts
    k = m.top_k
    has_bias = "router_bias" in p
    # serving sums the top-k slots, spread over the ranks, in float32
    # and rounds once, so the sum does not depend on which rank holds
    # which expert: a mesh serves the tokens a one-device mesh serves
    acc = jnp.float32 if shctx.get_serve_topology() is not None else dt

    def body(xb, router, bias, experts):
        rp = ({"router": router, "router_bias": bias}
              if bias is not None else {"router": router})
        xf = xb.reshape(-1, d)
        if ep_data > 1 and batch_sharded:
            # ---- full expert-parallel: local pack + all-to-all('data')
            flat_e, safe_pos, keep, flat_w, aux, C = _route_local(
                cfg, rp, xf, capacity_factor)
            buf, _ = _pack(xf, flat_e, safe_pos, keep, C, E, k=k)
            mi = jax.lax.axis_index("model")
            e_loc = E // (ep_data * ep_model)
            with jax.named_scope("moe.dispatch"):
                buf = jax.lax.all_to_all(buf, "data", split_axis=0,
                                         concat_axis=1, tiled=True)
                buf = jax.lax.dynamic_slice_in_dim(buf, mi * e_loc, e_loc,
                                                   0)
            out = _expert_ffn(cfg, experts, buf)
            if os.environ.get("REPRO_BASELINE"):
                # pre-§Perf: pad back to all model ranks' rows and a2a
                # ((ep_model-1)/ep_model of the reverse wire is zeros)
                with jax.named_scope("moe.combine"):
                    out_full = jnp.zeros((E // ep_data, C * ep_data, d),
                                         dt)
                    out_full = jax.lax.dynamic_update_slice_in_dim(
                        out_full, out, mi * e_loc, 0)
                    out_back = jax.lax.all_to_all(
                        out_full, "data", split_axis=1, concat_axis=0,
                        tiled=True)
                y = _combine(out_back, flat_e, safe_pos, flat_w, keep, k,
                             acc)
            else:
                # §Perf target 3: reverse a2a on the model-local slice
                # ONLY (16x wire saving on the reverse path).
                # out: (e_loc, C*ep_data, d) -> (ep_data*e_loc, C, d),
                # row di*e_loc+r = expert (di*ep_model+mi)*e_loc+r of MY
                # tokens.
                with jax.named_scope("moe.combine"):
                    out_back = jax.lax.all_to_all(
                        out, "data", split_axis=1, concat_axis=0,
                        tiled=True)
                mi_of_e = (flat_e // e_loc) % ep_model
                row = ((flat_e // (e_loc * ep_model)) * e_loc
                       + flat_e % e_loc)
                sel = keep & (mi_of_e == mi)
                y = _combine(out_back, flat_e, safe_pos, flat_w, sel, k,
                             acc, local_rows=row)
            with jax.named_scope("moe.combine"):
                y = jax.lax.psum(y, "model")   # sum expert shards
            aux = jax.lax.pmean(aux, dp_ax)
        elif batch_sharded and ep_data == 1 and f_tp:
            # ---- expert-FSDP: E over 'model', weight f-shards FSDP'd
            # over 'data'.  Tokens never move: each rank all-gathers the
            # (small) weight shards and processes its local tokens with
            # its local experts.  Gradients reduce-scatter automatically
            # (transpose of all_gather).
            with jax.named_scope("moe.experts"):
                ew = {kk: jax.lax.all_gather(
                    vv, "data", axis=(1 if kk == "w_down" else 2),
                    tiled=True) for kk, vv in experts.items()}
            flat_e, safe_pos, keep, flat_w, aux, C = _route_local(
                cfg, rp, xf, capacity_factor)
            e_loc = E // ep_model
            mi = jax.lax.axis_index("model")
            start = mi * e_loc
            buf_loc, sel = _pack(xf, flat_e, safe_pos, keep, C, e_loc,
                                 start=start, k=k)
            out = _expert_ffn(cfg, ew, buf_loc)
            y = _combine(out, flat_e, safe_pos, flat_w, sel, k, acc,
                         start=start)
            with jax.named_scope("moe.combine"):
                y = jax.lax.psum(y, "model")
            aux = jax.lax.pmean(aux, dp_ax)
        else:
            # ---- replicated-token fallback (unshardable batch, e.g.
            # long_500k B=1): every rank routes all tokens, computes its
            # local expert shard, partial sums reduce over sharded axes.
            flat_e, safe_pos, keep, flat_w, aux, C = _route_local(
                cfg, rp, xf, capacity_factor)
            e_loc = E // (ep_data * ep_model)
            mi = jax.lax.axis_index("model")
            start = mi * e_loc
            if ep_data > 1:
                di = jax.lax.axis_index("data")
                start = (di * ep_model + mi) * e_loc
            buf_loc, sel = _pack(xf, flat_e, safe_pos, keep, C, e_loc,
                                 start=start, k=k)
            ew = experts
            if f_tp:
                with jax.named_scope("moe.experts"):
                    ew = {kk: jax.lax.all_gather(
                        vv, "data", axis=(1 if kk == "w_down" else 2),
                        tiled=True) for kk, vv in experts.items()}
            out = _expert_ffn(cfg, ew, buf_loc)
            y = _combine(out, flat_e, safe_pos, flat_w, sel, k, acc,
                         start=start)
            red = ("model", "data") if ep_data > 1 else ("model",)
            with jax.named_scope("moe.combine"):
                y = jax.lax.psum(y, red)
            if has_pod:
                aux = jax.lax.pmean(aux, "pod")
        return y.astype(dt).reshape(xb.shape), aux

    wrapped = shard_map(
        body, mesh=mesh,
        in_specs=(bspec, rspec, P(None) if has_bias else None, especs),
        out_specs=(bspec, P()),
        **shard_map_kwargs(check_vma=False))
    y, aux = wrapped(x, p["router"], p.get("router_bias"), p["experts"])

    if m.num_shared_experts:
        y = y + _shared(cfg, p["shared"], x)
    return y, aux
