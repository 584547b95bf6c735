"""Paged flash-decode — Pallas TPU kernel for the serving hot path.

Fuses the page-table gather with the online-softmax attention inner
loop: the XLA reference path (``models.attention``: ``paged_read`` →
``masked_attention``) first materialises a slot-major
``(B, table_width * page_size, ...)`` gather of the token-major pool
and then attends over it — two passes over the slot's KV bytes and a
full-width softmax.  Here the page table is a SCALAR-PREFETCH operand
(``pltpu.PrefetchScalarGridSpec``): the KV BlockSpec index map reads
``table[b, w]`` to stream each physical page straight from the pool
into VMEM, so the gather never exists as a tensor and each page's
scores fold into the running (max, sum, accumulator) as it arrives.

Grid: ``(B, hk, W)`` (MLA: ``(B, W)``) with the page axis innermost and
sequential — the online-softmax state lives in VMEM scratch across the
W steps, exactly the ``kernels.flash_attention`` schedule with the
block index indirected through the page table.

Masking follows the paged contract (see ``models.attention``):
  * per-slot causal — key at logical position ``t`` (page ``w`` holds
    ``w*page_size + [0, page_size)``) is visible to query ``(b, s)``
    iff ``t <= q_positions[b, s]`` (sliding window when set);
  * pages past a slot's write head are NEVER visible (every visible
    position has been written by the slot), so unallocated table
    entries (0 = the trash page) only back positions the mask already
    kills — trash-page garbage cannot leak into the output;
  * fully-masked pages are skipped with ``pl.when`` (no MXU work), so
    a slot pays for the pages it has written, not the table width.

Multi-query verify shape: speculative decode's verify forward is this
same kernel at query width ``S = spec_decode`` — a q-block of S rows
per (slot, kv-head) grid step with per-query positions, exactly the
shape prefill chunks already lower.  The per-query-row causal mask is
what makes the scheduler's rewind-rollback sound: stale K/V written by
rejected drafts sits at positions strictly greater than every live
query's position, so it is invisible until the next verify chunk
overwrites it in place.

GQA head-group tiling: queries are laid out ``(B, hk, g*S, hd)`` so
one grid step attends a whole kv-head's group against its page — the
MXU tile is ``(g*S, hd) x (hd, page_size)``.  The kernel sees the pool
as ``(n_pages, page_size, hk*hd)`` (a free reshape of the token-major
``(N, hk, hd)`` leaf), and kv head ``h`` of a page is the
``(page_size, hd)`` block at column block ``h``: on the TPU this needs
``page_size % 8 == 0`` and ``hd % 128 == 0`` (or ``hk == 1``).  The absorbed-MLA variant
scores ``q_latent·ckv + q_rope·krope`` against the latent pool
(one kv head, ``dv = kv_lora_rank``) and returns the latent-space
output for the caller's ``w_uv`` up-projection.

Compiled for the CPU, and only there, the kernels run in interpret
mode (plain-JAX lowering: jit-able, scan-able, GSPMD-partitionable —
the serve-mesh tests run them under the (data, model) topology); for
a TPU they lower to Mosaic (``kernels.ops.run_pallas``).  Numerics: fp32 scores and accumulation
like the XLA path; the block-ordered online softmax is not bit-identical
to the flat softmax, but greedy argmax outputs are (pinned by
``tests/test_paged_decode.py`` on host and mesh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import run_pallas

NEG_INF = -1e30

__all__ = ["paged_flash_decode", "paged_flash_decode_mla"]


def _pin(*xs):
    """Pin every kernel operand (and, at the other end, the raw output)
    fully replicated under a serve topology.  The interpret-mode grid
    loop is a scan whose VMEM scratch the CPU SPMD partitioner reshards
    between steps when ANY operand — q, the pools, the page table or
    the positions — carries a sharding ("involuntary full
    rematerialization" warnings, wrong numbers; positions arrive
    sequence-sharded whenever ``constrain_bsd`` split the prefill chunk
    over "data").  Pinning at the pallas_call boundary keeps the fused
    loop whole; pool STORAGE stays model-sharded (the pin is the
    all-gather the XLA path pays at ``paged_read``).  Host mesh: no-op.
    """
    from repro.sharding.ctx import replicate_for_kernel
    return tuple(replicate_for_kernel(x) for x in xs)


def _pages(pool, page_table, page_size, layer, *, merge_heads=False):
    """The pool as ``(pages, page_size, features)`` and the page table
    that addresses it, as ``models.attention.pool_pages`` reads it: a
    scanned block's stacked pool ``(n_rep, N, ...)`` in place, at layer
    ``layer``'s offset in the scalar-prefetch table, where that is free.
    ``merge_heads`` folds GQA's ``(hk, hd)`` into the lane dim, which on
    the TPU moves every element it is given: the layer's rows are sliced
    out first, so one layer is moved and not the whole stack."""
    from repro.models.attention import PagedView, pool_pages
    if merge_heads:
        if layer is not None:
            pool = jax.lax.dynamic_index_in_dim(pool, layer, keepdims=False)
            layer = None
        pool = pool.reshape(pool.shape[:-2] + (-1,))
    return pool_pages(pool, PagedView(page_table.astype(jnp.int32),
                                      page_size, layer))


def _row_positions(pos_row, g, seq_q, rows):
    """Per-query positions for the (g, S)-flattened row layout.

    pos_row: (1, S) int32 loaded from VMEM.  Rows r in [0, g*S) map to
    query s = r % S; padding rows (MXU row alignment) get -1 so the
    mask kills them.  Built from iotas and a masked lane reduction:
    Mosaic has no (g, S) -> (g*S, 1) reshape.
    """
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, seq_q), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (rows, seq_q), 1)
    live = r < g * seq_q
    qpos = jnp.sum(jnp.where(live & (r % seq_q == s), pos_row, 0),
                   axis=1, keepdims=True)
    return jnp.where(live[:, :1], qpos, -1)              # (rows, 1)


def _online_update(s, v, m_scr, l_scr, acc_scr):
    """Fold one page's fp32 scores s: (rows, ps) and values v: (ps, dv)
    into the running (max, sum, accumulator) scratch."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = (acc_scr[...] * alpha[:, None]
                    + jax.lax.dot(p, v.astype(jnp.float32),
                                  preferred_element_type=jnp.float32))
    m_scr[...] = m_new


def _finish(l_scr, acc_scr, o_ref):
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)                      # fully-masked rows
    o_ref[...] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _page_mask(qpos2, w, page_size, window):
    """(rows, ps) visibility of page w's logical positions."""
    kv_pos = (w * page_size
              + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1))
    mask = kv_pos <= qpos2
    if window:
        mask &= kv_pos > qpos2 - window
    mask &= qpos2 >= 0                                   # padding rows
    return mask


def _page_visible(pos_row, w, page_size, window):
    """Block-level skip test: page w intersects [max-window, max] of the
    slot's query positions (positions are never negative on the paged
    decode path — idle slots freeze theirs)."""
    visible = w * page_size <= jnp.max(pos_row)
    if window:
        visible = jnp.logical_and(
            visible,
            (w + 1) * page_size - 1 > jnp.min(pos_row) - window)
    return visible


def _gqa_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale, page_size, g, seq_q,
                rows, n_pages_per_slot, window):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos_row = pos_ref[...]                               # (1, S)
    qpos2 = _row_positions(pos_row, g, seq_q, rows)

    @pl.when(_page_visible(pos_row, w, page_size, window))
    def _step():
        q = q_ref[...].astype(jnp.float32)               # (rows, hd)
        # the pool is read in its own dtype: round to q's first, as a
        # cast of the whole pool to the compute dtype would
        k = k_ref[...].astype(q_ref.dtype).astype(jnp.float32)  # (ps, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (rows, ps)
        s = jnp.where(_page_mask(qpos2, w, page_size, window), s, NEG_INF)
        _online_update(s, v_ref[...].astype(q_ref.dtype), m_scr, l_scr,
                       acc_scr)

    @pl.when(w == n_pages_per_slot - 1)
    def _done():
        _finish(l_scr, acc_scr, o_ref)


def _row_pad(rows):
    """Round the query-row tile up to the fp32 sublane multiple."""
    return max(8, -(-rows // 8) * 8)


def paged_flash_decode(q, k_pool, v_pool, page_table, q_positions, *,
                       page_size, window=0, layer=None):
    """Fused paged-gather + flash attention for GQA decode.

    q: (B, S, h, hd) — S is a decode token or a prefill chunk;
    k_pool, v_pool: (N, hk, hd) token-major page pool, or with `layer`
    a scanned block's stacked (n_rep, N, hk, hd) pool, read in place;
    page_table: (B, W) int32 physical page ids (0 = trash page);
    q_positions: (B, S) per-slot logical positions.
    Returns (B, S, h, hd) in q.dtype.
    """
    B, S, h, hd = q.shape
    hk = k_pool.shape[-2]
    g = h // hk
    W = page_table.shape[1]
    rows = _row_pad(g * S)
    scale = 1.0 / np.sqrt(hd)

    # (B, S, h, hd) -> (B, hk, g*S, hd): kv head's whole group as one tile
    qr = q.reshape(B, S, hk, g, hd).transpose(0, 2, 3, 1, 4).reshape(
        B, hk, g * S, hd)
    if rows > g * S:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - g * S), (0, 0)))
    # (N, hk, hd) -> (n_pages, page_size, hk*hd): the kv-head axis goes
    # into the lane dim, so kv head h_ of a page is the (page_size, hd)
    # block at column block h_ — a block shape the TPU tiling accepts
    # (a squeezed head axis in the second-minor position is refused)
    kp, table = _pages(k_pool, page_table, page_size, layer,
                       merge_heads=True)
    vp, _ = _pages(v_pool, page_table, page_size, layer, merge_heads=True)
    pos = q_positions.astype(jnp.int32).reshape(B, 1, S)

    kernel = functools.partial(
        _gqa_kernel, scale=scale, page_size=page_size, g=g, seq_q=S,
        rows=rows, n_pages_per_slot=W, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, hk, W),
        in_specs=[
            pl.BlockSpec((None, 1, S), lambda b, h_, w, t: (b, 0, 0)),
            pl.BlockSpec((None, None, rows, hd),
                         lambda b, h_, w, t: (b, h_, 0, 0)),
            pl.BlockSpec((None, page_size, hd),
                         lambda b, h_, w, t: (t[b, w], 0, h_)),
            pl.BlockSpec((None, page_size, hd),
                         lambda b, h_, w, t: (t[b, w], 0, h_)),
        ],
        out_specs=pl.BlockSpec((None, None, rows, hd),
                               lambda b, h_, w, t: (b, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    out = run_pallas(lambda interp: pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hk, rows, hd), q.dtype),
        interpret=interp,
    ), *_pin(table, pos, qr, kp, vp))
    out, = _pin(out)
    return (out[:, :, :g * S]
            .reshape(B, hk, g, S, hd).transpose(0, 3, 1, 2, 4)
            .reshape(B, S, h, hd))


def _mla_kernel(table_ref, pos_ref, ql_ref, qr_ref, ckv_ref, kr_ref,
                o_ref, m_scr, l_scr, acc_scr, *, scale, page_size, g,
                seq_q, rows, n_pages_per_slot, window):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos_row = pos_ref[...]
    qpos2 = _row_positions(pos_row, g, seq_q, rows)

    @pl.when(_page_visible(pos_row, w, page_size, window))
    def _step():
        ckv = ckv_ref[...].astype(ql_ref.dtype).astype(jnp.float32)
        # absorbed scores: latent dot + decoupled-rope dot, one page
        s = jax.lax.dot_general(
            ql_ref[...].astype(jnp.float32), ckv,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(
            qr_ref[...].astype(jnp.float32),
            kr_ref[...].astype(qr_ref.dtype).astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s *= scale
        s = jnp.where(_page_mask(qpos2, w, page_size, window), s, NEG_INF)
        _online_update(s, ckv, m_scr, l_scr, acc_scr)    # V == latent

    @pl.when(w == n_pages_per_slot - 1)
    def _done():
        _finish(l_scr, acc_scr, o_ref)


def paged_flash_decode_mla(q_lat, q_rope, ckv_pool, krope_pool,
                           page_table, q_positions, *, page_size, scale,
                           window=0, layer=None):
    """Absorbed-MLA variant: attend in the latent space against the
    compressed pool (one kv head; V is the latent itself).

    q_lat: (B, S, h, r) — q_nope absorbed through w_uk;
    q_rope: (B, S, h, rope_dim); ckv_pool: (N, r); krope_pool:
    (N, rope_dim) — or with `layer` a scanned block's stacked
    (n_rep, N, ·) pools, read in place; scale — 1/sqrt(nope+rope), the
    caller's convention.
    Returns the latent-space output (B, S, h, r) in q_lat.dtype for the
    caller's ``w_uv`` up-projection.
    """
    B, S, h, r = q_lat.shape
    rope_dim = q_rope.shape[-1]
    W = page_table.shape[1]
    rows = _row_pad(h * S)

    # one kv head: all h query heads share every page -> (B, h*S, ·)
    qlr = q_lat.reshape(B, S, h, r).transpose(0, 2, 1, 3).reshape(
        B, h * S, r)
    qrr = q_rope.reshape(B, S, h, rope_dim).transpose(0, 2, 1, 3).reshape(
        B, h * S, rope_dim)
    if rows > h * S:
        qlr = jnp.pad(qlr, ((0, 0), (0, rows - h * S), (0, 0)))
        qrr = jnp.pad(qrr, ((0, 0), (0, rows - h * S), (0, 0)))
    ckv, table = _pages(ckv_pool, page_table, page_size, layer)
    krp, _ = _pages(krope_pool, page_table, page_size, layer)
    pos = q_positions.astype(jnp.int32).reshape(B, 1, S)

    kernel = functools.partial(
        _mla_kernel, scale=scale, page_size=page_size, g=h, seq_q=S,
        rows=rows, n_pages_per_slot=W, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, W),
        in_specs=[
            pl.BlockSpec((None, 1, S), lambda b, w, t: (b, 0, 0)),
            pl.BlockSpec((None, rows, r), lambda b, w, t: (b, 0, 0)),
            pl.BlockSpec((None, rows, rope_dim), lambda b, w, t: (b, 0, 0)),
            pl.BlockSpec((None, page_size, r),
                         lambda b, w, t: (t[b, w], 0, 0)),
            pl.BlockSpec((None, page_size, rope_dim),
                         lambda b, w, t: (t[b, w], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, rows, r), lambda b, w, t: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows, r), jnp.float32),
        ],
    )
    out = run_pallas(lambda interp: pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, r), q_lat.dtype),
        interpret=interp,
    ), *_pin(table, pos, qlr, qrr, ckv, krp))
    out, = _pin(out)
    return (out[:, :h * S]
            .reshape(B, h, S, r).transpose(0, 2, 1, 3))
