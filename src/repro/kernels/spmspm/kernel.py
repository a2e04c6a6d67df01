"""Pallas SpMSpM kernel: tiled index-stream intersection (paper Fig. 6c).

Occamy mechanism: two SUs merge-intersect the sorted index streams of a CSR
row of A and a CSC column of B; the FPU multiply-accumulates on matches, and
the paper scores the comparator array by *index comparison rate* (GCOMP/s).

TPU translation: merge loops are serial and hostile to the VPU, so the
comparator array is re-shaped into what the VPU does natively -- **broadcast
all-pairs comparison of index tiles**: for stream positions (p, q), one
lane-dense (rt x ct) vector `==` compares key p of rt rows of A with key q of
ct columns of B. Rows of A (padded-ELL, sorted keys) meet columns of B;
matches gate a multiply-accumulate into a dense (rt x ct) output tile
resident in VMEM. GCOMP/s maps to VPU comparison throughput; utilization
is useful/issued comparisons (reported by ``ops.comparison_stats``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import INVALID_KEY


def _spmspm_kernel(ak_ref, av_ref, bk_ref, bv_ref, o_ref, *, rt, ct, la, lb,
                   as_ref=None):
    ak = ak_ref[...]                      # (rt, la) int32 sorted keys
    av = av_ref[...].astype(jnp.float32)  # (rt, la)
    if as_ref is not None:
        # BlockQuant dequant of the narrow A row stream: one f32 scale per
        # row, ``values.astype(f32) * scale`` -- verbatim the host
        # dequantize_rows contract, so narrow A values are bit-identical to
        # dequantizing on host and running the f32 kernel.
        av = av * as_ref[...]             # (rt, la) * (rt, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rt, la), 1)

    def a_step(p, acc):
        # Column p of the A row stream as an (rt, 1) vector: a masked lane
        # sum (one live term per row, so it is exact) -- Mosaic has no
        # dynamic lane slice.
        sel = lane == p
        a_key = jnp.sum(jnp.where(sel, ak, 0), axis=1, keepdims=True)
        a_val = jnp.sum(jnp.where(sel, av, 0.0), axis=1, keepdims=True)
        live = a_key != INVALID_KEY

        def b_step(q, part):
            # Comparator array step: keys of A at stream position p vs
            # position q of every B column in the tile, lane-dense.
            b_key = bk_ref[pl.ds(q, 1), :]                          # (1, ct)
            b_val = bv_ref[pl.ds(q, 1), :].astype(jnp.float32)      # (1, ct)
            eq = (a_key == b_key) & live                            # (rt, ct)
            return part + jnp.where(eq, a_val * b_val, 0.0)

        # keys are unique within a row and within a column, so at most one
        # q matches per (r, c): the inner sum is exact in any order
        part = jax.lax.fori_loop(0, lb, b_step,
                                 jnp.zeros((rt, ct), jnp.float32))
        return acc + part

    acc = jax.lax.fori_loop(0, la, a_step, jnp.zeros((rt, ct), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


def _spmspm_quant_kernel(ak_ref, av_ref, as_ref, bk_ref, bv_ref, o_ref, *,
                         rt, ct, la, lb):
    _spmspm_kernel(ak_ref, av_ref, bk_ref, bv_ref, o_ref,
                   rt=rt, ct=ct, la=la, lb=lb, as_ref=as_ref)


def spmspm_ell(a_keys: jax.Array, a_vals: jax.Array,
               b_keys: jax.Array, b_vals: jax.Array, *,
               rt: int = 8, ct: int = 8, nt: int = 1, out_dtype=jnp.float32,
               interpret: bool = False,
               a_scales: jax.Array | None = None) -> jax.Array:
    """C[r, c] = sum over key matches of A-row r and B-col c.

    a_keys/a_vals: (R, La) padded-ELL rows of A (keys ascending, INVALID pad).
    b_keys/b_vals: (C, Lb) padded-ELL *columns* of B.
    a_scales: (R,) or (R, 1) f32 per-row dequant scales for narrow (fp8/int8)
    ``a_vals`` (BlockQuant over the row stream); None keeps the wide path
    byte-identical to the pre-quant kernel.
    ``nt``: output-column residency -- one grid step holds an (rt, nt*ct)
    output tile resident and intersects against an (nt*ct, lb) B-stream
    block, so the A row stream (the serial ``la`` walk) runs once per ``nt``
    column tiles instead of once per tile.  Match accumulation per output
    element is unchanged (the ``la`` fori order), so any ``nt`` is
    bit-identical to ``nt=1``.
    Returns dense C (R, C); ``ops.py`` compacts to a sparse stream (the third
    SU's joint-index write-back).
    """
    R, la = a_keys.shape
    C, lb = b_keys.shape
    assert nt >= 1, nt
    wct = nt * ct
    assert R % rt == 0 and C % wct == 0, ((R, C), (rt, ct, nt))
    # B's column streams go in transposed, (lb, C): stream position q of a
    # tile of columns is then one lane-dense row.
    in_specs = [
        pl.BlockSpec((rt, la), lambda i, j: (i, 0)),
        pl.BlockSpec((rt, la), lambda i, j: (i, 0)),
        pl.BlockSpec((lb, wct), lambda i, j: (0, j)),
        pl.BlockSpec((lb, wct), lambda i, j: (0, j)),
    ]
    operands = [a_keys, a_vals, b_keys.T, b_vals.T]
    if a_scales is None:
        kern = functools.partial(_spmspm_kernel, rt=rt, ct=wct, la=la, lb=lb)
    else:
        kern = functools.partial(_spmspm_quant_kernel, rt=rt, ct=wct,
                                 la=la, lb=lb)
        in_specs.insert(2, pl.BlockSpec((rt, 1), lambda i, j: (i, 0)))
        operands.insert(2, a_scales.reshape(R, 1).astype(jnp.float32))
    return pl.pallas_call(
        kern,
        grid=(R // rt, C // wct),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rt, wct), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        interpret=interpret,
    )(*operands)
