"""Activation-sharding constraint context.

GSPMD left alone sometimes picks activation shardings that replicate
the batch (measured: 3-4x activation blowup on train_4k).  Production
JAX frameworks pin the residual stream with with_sharding_constraint;
we do the same, but only when a mesh has been registered (tests and
single-device runs stay constraint-free).

``set_activation_mesh(mesh)`` is called by the launcher/dry-run before
tracing; model code calls ``constrain_bsd(x)`` / ``constrain_logits``.
``activation_mesh(mesh)`` is the scoped form — launchers that may be
called in-process (tests, notebooks) must use it so a production mesh
never leaks into the caller's subsequent traces.

Serving additionally registers a :class:`ServeTopology` — the MaxText
``dcn_data_parallelism × ici_fsdp_parallelism`` split applied to
decode: data-parallel replica groups over the DCN-ish axes
(``"pod"``/``"data"``, each replica running its own scheduler batch)
and model-sharded decode over the ICI ``"model"`` axis (paged KV pools
split on the head/latent axis, so pool bytes/device drop ~1/mp).  The
serve topology rides the same scoping discipline as the activation
mesh (``serve_topology(...)`` sets both) and gates the paged-pool read
constraints (``constrain_paged_kv`` / ``constrain_paged_latent``).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_MESH = None
_TOPO = None


@dataclasses.dataclass(frozen=True)
class ServeTopology:
    """How a serving engine maps onto a device mesh.

    replica_axes — data-parallel replica groups (DCN): each replica
                   holds a full copy of the paged pool and serves its
                   own slots.
    model_axis   — tensor/expert-sharded decode (ICI): pool leaves,
                   attention heads and expert rows split here.
    """
    mesh: object
    replica_axes: tuple
    model_axis: object          # axis name, or None (host mesh)

    @classmethod
    def from_mesh(cls, mesh) -> "ServeTopology":
        reps = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        model = "model" if "model" in mesh.axis_names else None
        return cls(mesh=mesh, replica_axes=reps, model_axis=model)

    @property
    def replicas(self) -> int:          # dcn_data_parallelism
        n = 1
        for a in self.replica_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def model_parallel(self) -> int:    # ici model sharding of decode
        return self.mesh.shape[self.model_axis] if self.model_axis else 1


def get_serve_topology():
    return _TOPO


@contextlib.contextmanager
def serve_topology(topo):
    """Scope a serve topology AND its mesh as the activation mesh (the
    paged decode path is traced under both).  ``None`` clears both;
    previous values are restored on exit even when the body raises."""
    global _MESH, _TOPO
    prev_mesh, prev_topo = _MESH, _TOPO
    _MESH = topo.mesh if topo is not None else None
    _TOPO = topo
    try:
        yield topo
    finally:
        _MESH, _TOPO = prev_mesh, prev_topo


def set_activation_mesh(mesh) -> None:
    global _MESH
    _MESH = mesh


def get_activation_mesh():
    return _MESH


@contextlib.contextmanager
def activation_mesh(mesh):
    """Scope the activation-constraint mesh: set for the duration
    (``None`` explicitly clears it), always restore the previous value
    on exit — even when the body raises."""
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        yield mesh
    finally:
        _MESH = prev


def _dp_axes():
    return tuple(a for a in ("pod", "data") if a in _MESH.axis_names)


def _dp_size():
    n = 1
    for a in _dp_axes():
        n *= _MESH.shape[a]
    return n


def constrain(x, *axes):
    if _MESH is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, P(*axes)))


def constrain_bsd(x):
    """Residual stream (B, S, d): batch over pod×data when divisible,
    otherwise (long_500k B=1) shard the sequence over data."""
    if _MESH is None:
        return x
    ax = _dp_axes()
    spec_b = ax if len(ax) > 1 else ax[0]
    if x.shape[0] % _dp_size() == 0:
        return constrain(x, spec_b, None, None)
    if x.ndim >= 2 and x.shape[1] % _MESH.shape.get("data", 1) == 0 \
            and x.shape[1] > 1:
        return constrain(x, None, "data", None)
    return constrain(x, *([None] * x.ndim))


def constrain_ecd(x):
    """MoE dispatch buffers (E, C, ...): experts over (data×model) when
    divisible (expert-parallel), else model, else replicated."""
    if _MESH is None:
        return x
    E = x.shape[0]
    dsz = _MESH.shape.get("data", 1)
    msz = _MESH.shape.get("model", 1)
    if dsz > 1 and msz > 1 and E % (dsz * msz) == 0:
        ax = ("data", "model")
    elif msz > 1 and E % msz == 0:
        ax = "model"
    else:
        ax = None
    return constrain(x, ax, *([None] * (x.ndim - 1)))


def constrain_tokens(x):
    """Token-major tensors (N, ...): N over pod×data when divisible."""
    if _MESH is None:
        return x
    ax = _dp_axes()
    if x.shape[0] % _dp_size() == 0:
        return constrain(x, ax if len(ax) > 1 else ax[0],
                         *([None] * (x.ndim - 1)))
    return x


def constrain_logits(x):
    """(B, S, V): batch over dp, vocab over model."""
    if _MESH is None:
        return x
    ax = _dp_axes()
    spec_b = (ax if len(ax) > 1 else ax[0]) \
        if x.shape[0] % _dp_size() == 0 else None
    v_ok = x.shape[-1] % _MESH.shape.get("model", 1) == 0
    return constrain(x, spec_b, None, "model" if v_ok else None)


# --------------------------------------------------------------------------
# paged serving pool (spec-aware decode reads — gated on the topology)
# --------------------------------------------------------------------------

def _serve_model_size() -> int:
    if _TOPO is None or _TOPO.model_axis is None:
        return 1
    return _TOPO.model_parallel


def constrain_paged_kv(x):
    """Gathered paged K/V view (B, L, hk, hd): pin the pool's model
    sharding through the page-table gather — heads over "model" when
    they divide, head_dim otherwise (mirrors ``rules.pool_spec``), so
    GSPMD never round-trips the gathered view through replication."""
    mp = _serve_model_size()
    if mp <= 1:
        return x
    if x.shape[2] % mp == 0:
        return constrain(x, None, None, "model", None)
    if x.shape[3] % mp == 0:
        return constrain(x, None, None, None, "model")
    return x


def constrain_paged_latent(x):
    """Gathered paged MLA latent view (B, L, r): latent axis over
    "model" when it divides (the pool-leaf layout)."""
    mp = _serve_model_size()
    if mp <= 1 or x.shape[-1] % mp:
        return x
    return constrain(x, None, None, "model")


def serve_model_sharded() -> bool:
    """Whether a serve topology shards the model (and the paged pool's
    feature axes) over more than one device."""
    return _serve_model_size() > 1


def replicate_for_kernel(x):
    """Pin a Pallas interpret-mode kernel operand (or its result) fully
    replicated under a serve topology.  The interpreter lowers the grid
    to a loop carrying the VMEM scratch as scan state; the CPU SPMD
    partitioner reshards that carry between steps ("involuntary full
    rematerialization") and produces wrong numbers — the same bug class
    ``replicate_update`` works around.  Pinning the kernel's operands
    and output replicated keeps the fused loop out of the partitioner's
    hands; the pool STORAGE stays model-sharded (the pin inserts an
    all-gather at the consumption point, the analogue of the gathered
    view the XLA reference path materialises).  Host mesh: no-op."""
    if not serve_model_sharded():
        return x
    return constrain(x, *([None] * x.ndim))


def replicate_update(x):
    """Pin a paged-pool scatter UPDATE fully replicated.  The update is
    tiny (B x new-tokens), but letting GSPMD partition it along a
    feature axis that rope's split/concat just touched miscombines the
    halves when the scatter sits inside the layer ``lax.scan`` (the
    written K comes out exactly replica-count times too large on the
    CPU SPMD partitioner; a model-layout constraint on the update does
    NOT survive the scan).  Replicating the update makes the scatter
    partition trivially per pool shard.  Host mesh: no-op."""
    if _serve_model_size() <= 1:
        return x
    return constrain(x, *([None] * x.ndim))
