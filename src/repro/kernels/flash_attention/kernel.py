"""Pallas streaming (flash) attention: the LM-side SU-style kernel.

The same Occamy discipline applied to attention: affine K/V tile streams are
double-buffered into VMEM by the Pallas pipeline while the MXU runs
back-to-back (bq x d)(d x bk) products; the online-softmax state (m, l, acc)
lives in VMEM scratch across the KV grid dimension -- the SPM-resident
accumulator. Supports GQA (kv-head sharing), causal masking and sliding
windows (Gemma-3's 5:1 local:global = banded sparsity, same halo discipline
as the stencil kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.masks import KIND_CAUSAL, KIND_WINDOW, NEG_INF
from repro.kernels import streamwalk


def _flash_kernel(q_offset_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, causal: bool, window: int | None,
                  bq: int, bk: int, n_kv_tiles: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qi = pl.program_id(2)
    # q_offset: absolute position of this shard's first query row (scalar
    # prefetch) -- lets sequence-sharded callers (shard_map SP) keep exact
    # causal/window masks.
    off = q_offset_ref[0]
    q_pos = off + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # Tile-level skip: entirely-masked KV tiles cost zero FLOPs (the paper's
    # "only stream useful data" discipline).
    q_lo, q_hi = off + qi * bq, off + qi * bq + bq - 1
    k_lo, k_hi = ki * bk, ki * bk + bk - 1
    live = True
    if causal:
        live = live & (k_lo <= q_hi)
    if window is not None:
        live = live & (k_hi >= q_lo - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv_tiles - 1)
    def _final():
        l = l_ref[...]
        safe = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, bq: int = 128, bk: int = 128,
                    q_offset=None, interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D); Hq % Hkv == 0 (GQA).

    ``q_offset``: absolute position of q row 0 (scalar; default 0) for
    sequence-sharded callers. Returns (B, Hq, Sq, D) in q.dtype.
    Sq % bq == 0, Skv % bk == 0 (ops.py pads).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    n_kv = Skv // bk
    if q_offset is None:
        q_offset = jnp.zeros((1,), jnp.int32)
    else:
        q_offset = jnp.asarray(q_offset, jnp.int32).reshape(1)
    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             window=window, bq=bq, bk=bk, n_kv_tiles=n_kv)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, Sq // bq, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, h, qi, ki, off: (b, h, qi, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, qi, ki, off: (b, h // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, qi, ki, off: (b, h // g, ki, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bq, D), lambda b, h, qi, ki, off: (b, h, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        interpret=interpret,
    )(q_offset, q, k, v)


# ---------------------------------------------------------------------------
# Block-sparse attention: the BCSR stream-walk discipline applied to the KV
# grid.  A BlockMask (core.masks) lowers to sorted (row, col, kind) streams;
# the sparse kernel walks visible tiles only, the masked-dense kernel walks
# the full grid gated by the same per-tile kinds (the parity baseline).  Both
# share _tile_update, so they are bit-identical per construction.
# ---------------------------------------------------------------------------

def _tile_update(q, k, v, m_ref, l_ref, acc_ref, *, scale: float, kind,
                 q_pos, k_pos, window: int | None, skv: int):
    """One online-softmax update of the resident (m, l, acc) state with one
    (bq, bk) tile, refined per the tile's kind bits (core.masks).

    Dead-entry safety: with ``p = where(mask, exp(s - m_new), 0)`` a fully
    masked tile is an *exact* no-op -- m_new == m_prev, alpha == exp(0) == 1,
    p == 0 -- so bucket-padding entries and empty rows change nothing, and
    for live tiles the form is bit-identical to the classic
    exp-of-NEG_INF-masked update (the masked exp underflows to +0.0).
    """
    qf = q.astype(jnp.float32) * scale                 # (bq, d)
    kf = k.astype(jnp.float32)                         # (bk, d)
    vf = v.astype(jnp.float32)                         # (bk, d)
    s = jax.lax.dot_general(qf, kf, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (bq, bk)
    # The kind bits pick scalar bounds on q_pos - k_pos (causal: >= 0,
    # window: < window); an unset bit leaves that side open.  Mosaic cannot
    # select between two boolean vectors, so the refinement is an integer
    # compare rather than a where over masks.
    diff = q_pos - k_pos
    lo = jnp.where((kind & KIND_CAUSAL) != 0, 0, jnp.iinfo(jnp.int32).min)
    mask = (k_pos < skv) & (diff >= lo)                # KV tail validity
    if window is not None:
        hi = jnp.where((kind & KIND_WINDOW) != 0, window,
                       jnp.iinfo(jnp.int32).max)
        mask &= diff < hi
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(vf.dtype), vf, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _finalize(o_ref, l_ref, acc_ref):
    l = l_ref[...]
    safe = jnp.where(l == 0, 1.0, l)
    o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def _flash_masked_kernel(kinds_ref, q_offset_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float,
                         window: int | None, bq: int, bk: int, skv: int,
                         n_kv_tiles: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kind = kinds_ref[qi, ki]
    off = q_offset_ref[0]
    q_pos = off + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    @pl.when(kind >= 0)
    def _compute():
        _tile_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], m_ref, l_ref,
                     acc_ref, scale=scale, kind=kind, q_pos=q_pos,
                     k_pos=k_pos, window=window, skv=skv)

    @pl.when(ki == n_kv_tiles - 1)
    def _final():
        _finalize(o_ref, l_ref, acc_ref)


def flash_attention_masked(q: jax.Array, k: jax.Array, v: jax.Array,
                           tile_kinds: jax.Array, *, skv: int,
                           window: int | None = None,
                           scale: float | None = None, q_offset=None,
                           interpret: bool = False) -> jax.Array:
    """Dense-grid flash over a per-tile kind map: every KV tile is stepped,
    dead tiles (kind < 0) skip compute (the old whole-tile -1e30 masking,
    now stream-shaped).  The parity baseline for the sparse walk.

    q: (B, Hq, Sq_pad, D) with Sq_pad % bq == 0; k/v: (B, Hkv, Skv_pad, D)
    with Skv_pad % bk == 0; ``skv`` is the true (unpadded) KV length.
    tile_kinds: (Sq_pad//bq, Skv_pad//bk) int32 (BlockMask.tile_kinds).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv_pad, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    n_q, n_kv = tile_kinds.shape
    assert Sq % n_q == 0 and Skv_pad % n_kv == 0
    bq, bk = Sq // n_q, Skv_pad // n_kv
    scale = scale if scale is not None else D ** -0.5
    if q_offset is None:
        q_offset = jnp.zeros((1,), jnp.int32)
    else:
        q_offset = jnp.asarray(q_offset, jnp.int32).reshape(1)
    kern = functools.partial(_flash_masked_kernel, scale=scale, window=window,
                             bq=bq, bk=bk, skv=skv, n_kv_tiles=n_kv)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # tile_kinds, q_offset
            grid=(B, Hq, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, h, qi, ki, kinds, off: (b, h, qi, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, qi, ki, kinds, off:
                             (b, h // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, qi, ki, kinds, off:
                             (b, h // g, ki, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bq, D), lambda b, h, qi, ki, kinds, off: (b, h, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(tile_kinds, jnp.int32), q_offset, q, k, v)


def _flash_sparse_kernel(rows_ref, cols_ref, kinds_ref, q_offset_ref, q_ref,
                         k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         scale: float, window: int | None, bq: int, bk: int,
                         skv: int, nnzb: int):
    i = pl.program_id(2)  # position in the visible-tile stream

    @pl.when(streamwalk.row_start(rows_ref, i))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kind = kinds_ref[i]
    off = q_offset_ref[0]
    q_pos = off + rows_ref[i] * bq + \
        jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = cols_ref[i] * bk + \
        jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    @pl.when(kind >= 0)  # bucket-pad / empty-row entries are exact no-ops
    def _compute():
        _tile_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], m_ref, l_ref,
                     acc_ref, scale=scale, kind=kind, q_pos=q_pos,
                     k_pos=k_pos, window=window, skv=skv)

    @pl.when(streamwalk.row_end(rows_ref, i, nnzb))
    def _final():
        _finalize(o_ref, l_ref, acc_ref)


def flash_attention_sparse(q: jax.Array, k: jax.Array, v: jax.Array,
                           rows: jax.Array, cols: jax.Array,
                           kinds: jax.Array, *, skv: int,
                           window: int | None = None,
                           scale: float | None = None, bq: int = 128,
                           bk: int = 128, q_offset=None,
                           interpret: bool = False) -> jax.Array:
    """Flash attention walking a BlockMask's visible-tile stream.

    The KV grid dimension is the *stream walk*: scalar-prefetched
    (row, col, kind) indices (``BlockMask.lower()``, bucket-padded to a
    power of two like the MoE dispatch stream) steer the K/V BlockSpec DMA
    (SU indirection) while the online-softmax (m, l, acc) state stays
    VMEM-resident across each q-row's run.  Whole-tile masking disappears --
    only intra-tile causal/window/tail edges remain, selected per tile by
    the kind bits.

    q: (B, Hq, Sq_pad, D), Sq_pad % bq == 0; k/v: (B, Hkv, Skv_pad, D),
    Skv_pad % bk == 0; ``skv`` is the true KV length.  rows/cols/kinds:
    (capacity,) int32, sorted by (row, col), every q-tile row present.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv_pad, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    assert Sq % bq == 0 and Skv_pad % bk == 0
    nnzb = rows.shape[0]
    scale = scale if scale is not None else D ** -0.5
    if q_offset is None:
        q_offset = jnp.zeros((1,), jnp.int32)
    else:
        q_offset = jnp.asarray(q_offset, jnp.int32).reshape(1)
    walk = streamwalk.StreamWalk(outer=2)  # (b, h) outer, stream axis last
    kern = functools.partial(_flash_sparse_kernel, scale=scale, window=window,
                             bq=bq, bk=bk, skv=skv, nnzb=nnzb)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # rows, cols, kinds, q_offset
            grid=walk.grid((B, Hq), nnzb),
            in_specs=[
                # Q / output revisit the sorted row stream: the tile stays
                # resident across its run of KV blocks.
                walk.row_spec((1, 1, bq, D),
                              lambda o, r, t: (o[0], o[1], r, 0)),
                # K/V: the indirect stream -- the prefetched block-col index
                # steers which KV tile the pipeline double-buffers next.
                walk.indexed_spec((1, 1, bk, D),
                                  lambda o, c, t: (o[0], o[1] // g, c, 0)),
                walk.indexed_spec((1, 1, bk, D),
                                  lambda o, c, t: (o[0], o[1] // g, c, 0)),
            ],
            out_specs=walk.row_spec((1, 1, bq, D),
                                    lambda o, r, t: (o[0], o[1], r, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
      jnp.asarray(kinds, jnp.int32), q_offset, q, k, v)
