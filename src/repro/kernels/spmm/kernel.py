"""Pallas BCSR x dense SpMM: the SU-indirection kernel (paper Fig. 5 / 6b).

Occamy mechanism: an SU streams the sparse row's column indices; a second SU
uses them as an *indirect* stream into the dense operand, so the FPU executes
back-to-back FMAs. TPU translation: the block-column index stream is *scalar
prefetched* and drives the BlockSpec ``index_map`` of the dense operand -- the
index stream literally steers the DMA engine one tile ahead of compute
(``PrefetchScalarGridSpec``), while the MXU consumes (bm x bk) x (bk x bn)
tiles back-to-back.

Output residency (``nt``): the accumulator block is ``nt`` N-tiles wide --
(bm, nt*bn) resident in VMEM -- and the grid walks the nonzero-block stream
once per ``nt`` output tiles instead of once per tile.  The grid is
(N / (nt*bn), nnzb, nt) with the sub-tile dim innermost: the A-block spec's
index map is constant across the ``t`` steps, so the Pallas pipeline fetches
each stream block ONCE per ``i`` while the dense operand keeps streaming one
(bk, bn) K-tile per step (double-buffered by the pipeline, steered by the
scalar-prefetched column index).  Stream re-reads drop from ``N/bn`` to
``N/(nt*bn)`` -- Occamy's SPM-resident accumulation widened across the
output row.

Output revisiting: the block stream is sorted by block-row, so for a fixed
N-supertile the output block index is non-decreasing across the inner grid
dims; Pallas keeps the accumulator tile resident in VMEM until the row
changes (first-visit zeroing via ``pl.when``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import streamwalk


def _spmm_kernel(brows_ref, bcols_ref, blocks_ref, b_ref, o_ref, *,
                 bn: int, nt: int, scales_ref=None):
    i = pl.program_id(1)  # position in the nonzero-block stream
    t = pl.program_id(2)  # which resident N-subtile this step accumulates

    @pl.when(streamwalk.row_start(brows_ref, i) & (t == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = blocks_ref[0]          # (bm, bk)
    if scales_ref is not None:
        # BlockQuant dequant: one scale multiply per stream block, computed
        # as ``values.astype(f32) * scale`` -- verbatim the host dequantize
        # contract, so the narrow path is bit-identical to dequantizing on
        # host and running the f32 kernel.
        a = a.astype(jnp.float32) * scales_ref[0]
    b = b_ref[...]             # (bk, bn)
    # f32 operands are multiplied at f32 accuracy (HIGHEST), not as one bf16
    # MXU pass (the default), so f32 and dequantized values keep their
    # precision and 0/1 dispatch blocks copy f32 activations exactly.  bf16
    # products are exact at the default (Mosaic refuses HIGHEST for them).
    f32 = jnp.result_type(a, b) == jnp.float32
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST if f32 else None
                  ).astype(o_ref.dtype)
    if nt == 1:
        o_ref[...] += acc
    else:
        # static unroll over the resident sub-tiles: exactly one branch fires
        # per step, each with a static (lane-aligned) store offset.
        for tt in range(nt):
            @pl.when(t == tt)
            def _acc(tt=tt):
                o_ref[:, tt * bn:(tt + 1) * bn] += acc


def _spmm_quant_kernel(brows_ref, bcols_ref, blocks_ref, scales_ref, b_ref,
                       o_ref, *, bn: int, nt: int):
    _spmm_kernel(brows_ref, bcols_ref, blocks_ref, b_ref, o_ref,
                 bn=bn, nt=nt, scales_ref=scales_ref)


def spmm_bcsr(block_rows: jax.Array, block_cols: jax.Array, blocks: jax.Array,
              dense: jax.Array, *, n_block_rows: int, bn: int = 128,
              nt: int = 1, out_dtype=jnp.float32,
              interpret: bool = False,
              scales: jax.Array | None = None) -> jax.Array:
    """C = A @ dense where A is streamed as flattened BCSR blocks.

    Args:
      block_rows / block_cols: (nnzb,) int32, sorted by (row, col); every
        block-row must appear at least once (ops.py pads empty rows).
      blocks: (nnzb, bm, bk).
      dense: (K, N) with K = n_block_cols * bk, N % (nt * bn) == 0.
      n_block_rows: number of block rows of A (static).
      nt: output-residency width -- how many (bm, bn) N-tiles of the output
        row stay VMEM-resident per stream walk (1 = the classic kernel).
      scales: (nnzb,) or (nnzb, 1) f32 per-block dequant scales for narrow
        (fp8/int8) ``blocks`` (BlockQuant); None keeps the wide path
        byte-identical to the pre-quant kernel.
    Returns:
      (n_block_rows * bm, N) in ``out_dtype``.
    """
    nnzb, bm, bk = blocks.shape
    K, N = dense.shape
    assert nt >= 1, nt
    assert N % (nt * bn) == 0, (N, bn, nt)
    # j outer (N-supertile), i middle (stream walk), t inner (resident
    # sub-tile): per-row accumulation stays contiguous, and the A-block index
    # map is constant in t so each stream block is DMA'd once per i.
    walk = streamwalk.StreamWalk(outer=1, inner=1)
    grid = walk.grid((N // (nt * bn),), nnzb, (nt,))

    in_specs = [
        # A-block stream: affine walk of the flattened block array;
        # constant across t -> one fetch per stream position.
        walk.stream_spec((1, bm, bk)),
        # Dense operand: the *indirect* stream -- block-col index
        # steers which K-tile the DMA fetches (SU indirection); the
        # pipeline double-buffers the next (bk, bn) tile while the
        # MXU consumes the current one.
        walk.indexed_spec((bk, bn), lambda o, col, t: (col, o[0] * nt + t[0])),
    ]
    operands = [block_rows, block_cols, blocks, dense]
    if scales is None:
        kern = functools.partial(_spmm_kernel, bn=bn, nt=nt)
    else:
        # Scale stream rides the same affine walk as the A blocks (one
        # (1, 1) tile per stream position, constant across t).  The array is
        # (nnzb, 1, 1) so the block's last two dims equal the array's, as
        # Mosaic requires of a block narrower than the (8, 128) tiling.
        kern = functools.partial(_spmm_quant_kernel, bn=bn, nt=nt)
        in_specs.insert(1, walk.stream_spec((1, 1, 1)))
        operands.insert(3, scales.reshape(nnzb, 1, 1).astype(jnp.float32))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_rows, block_cols
            grid=grid,
            in_specs=in_specs,
            out_specs=walk.row_spec((bm, nt * bn), lambda o, row, t: (row, o[0])),
        ),
        out_shape=jax.ShapeDtypeStruct((n_block_rows * bm, N), out_dtype),
        interpret=interpret,
    )(*operands)


def stream_walks(n: int, bn: int, nt: int) -> int:
    """How many times one call re-walks the index/block stream: the reread
    factor ``ceil(N / (nt*bn))`` (1 == the whole stream is read once)."""
    return -(-n // (nt * bn))
