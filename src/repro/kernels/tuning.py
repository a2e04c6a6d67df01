"""Tile-size autotune table for the sparse / stencil Pallas kernels.

Occamy fixes its working-set geometry at silicon time (128 KiB TCDM per
cluster, 8-lane FPU SIMD); the TPU analogue is choosing Pallas block shapes
so one (A-block, B-tile, accumulator) working set fits VMEM while the MXU/VPU
tiles stay aligned to the native (8, 128) lane quantum.  This module replaces
the hardcoded ``bn=128`` / ``rt=ct=8`` / stencil-tile defaults scattered
through the ops layers with a single provenance-tracked table.

Provenance: entries were selected by sweeping interpret-mode correctness on
CPU and a hand roofline model for TPU shapes (VMEM budget ~16 MiB/core, MXU
128x128, VPU 8x128).  They are *static* heuristics, not on-device
measurements -- re-measure when real TPU time is available and override via
:func:`register`.

Selection contract:
  * ``lookup("spmm", ...)``    -> {"bn": int}
  * ``lookup("spmspm", ...)``  -> {"rt": int, "ct": int}
  * ``lookup("stencil", ...)`` -> {"tile": Tuple[int, ...]}

On CPU (no TPU backend) every op falls back to the smallest aligned tile:
interpret mode emulates the grid serially, so large tiles only add padding
waste without any DMA-overlap benefit.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# Native lane quanta: second-minor x minor tile of the VPU / MXU.
SUBLANE = 8
LANE = 128
# Per-core VMEM budget we allow one kernel working set to occupy (bytes).
VMEM_BUDGET = 8 * 2**20


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    """True when the default backend is a TPU (tuning targets VMEM);
    otherwise the CPU/interpret row is used.  A backend that fails to
    initialize raises here rather than passing for a CPU."""
    return jax.default_backend() == "tpu"


def _dtype_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


# ---------------------------------------------------------------------------
# Table rows.  Key: (op, dtype-bucket, platform) -> params.  dtype-bucket is
# the accumulating-input width ("f32" for >=4-byte, "bf16" for 2-byte,
# "i8/fp8" for 1-byte); platform is "tpu" or "cpu".
# ---------------------------------------------------------------------------

def _bucket(dtype) -> str:
    b = _dtype_bytes(dtype)
    return "f32" if b >= 4 else ("bf16" if b == 2 else "fp8")


_TABLE: Dict[Tuple[str, str, str], Dict[str, Any]] = {
    # SpMM: bn is the dense-operand N-tile; nt is the output-residency width
    # (how many N-tiles of one output row stay VMEM-resident per walk of the
    # index/block stream -- the stream reread factor is N / (nt*bn)).  Wider
    # tiles amortize the per-step index-stream scalar read; narrower dtypes
    # double the lane capacity so the same VMEM footprint covers 2x/4x the
    # columns.  CPU/interpret rows pin nt=1: the grid is emulated serially,
    # so residency buys nothing and only adds padding waste.
    ("spmm", "f32", "tpu"): {"bn": 256, "nt": 4},
    ("spmm", "bf16", "tpu"): {"bn": 512, "nt": 4},
    ("spmm", "fp8", "tpu"): {"bn": 512, "nt": 4},
    ("spmm", "f32", "cpu"): {"bn": 128, "nt": 1},
    ("spmm", "bf16", "cpu"): {"bn": 128, "nt": 1},
    ("spmm", "fp8", "cpu"): {"bn": 128, "nt": 1},
    # SpMSpM: (rt, ct) is the dense accumulator tile; the all-pairs compare
    # issues rt*ct*Lb comparisons per step, so bigger tiles raise comparator
    # occupancy until the (rt, la) + (ct, lb) streams blow VMEM.  nt widens
    # the *output-column* residency: one kernel step computes (rt, nt*ct)
    # against an (nt*ct, lb) B-stream block, walking the A row stream once
    # per nt column tiles instead of once per tile.  On TPU the (rt, nt*ct)
    # output block is lane-major, so nt*ct is a multiple of 128 (or spans
    # the whole, clamped C).
    ("spmspm", "f32", "tpu"): {"rt": 16, "ct": 128, "nt": 1},
    ("spmspm", "bf16", "tpu"): {"rt": 16, "ct": 128, "nt": 1},
    ("spmspm", "fp8", "tpu"): {"rt": 16, "ct": 128, "nt": 1},
    ("spmspm", "f32", "cpu"): {"rt": 8, "ct": 8, "nt": 1},
    ("spmspm", "bf16", "cpu"): {"rt": 8, "ct": 8, "nt": 1},
    ("spmspm", "fp8", "cpu"): {"rt": 8, "ct": 8, "nt": 1},
    # MoE dispatch-as-SpMM (models.moe "bcsr" backend): ``block`` tiles the
    # 0/1 (slot, token) dispatch matrix -- small square blocks track the
    # one-nonzero-per-column structure; ``bn`` is the d_model N-tile of the
    # token operand streamed through the SpMM kernel.  ``min_bucket`` is the
    # floor of the power-of-two nnzb bucket a routed index stream is padded
    # to for a compiled step (engine.stream_bucket): larger floors mean
    # fewer recompiles at the cost of more zero-block stream work, so the
    # TPU row (compiles are expensive, streams are cheap) sits higher than
    # the CPU/interpret row.
    ("moe_dispatch", "f32", "tpu"): {"block": (8, 8), "bn": 256,
                                     "min_bucket": 32, "nt": 2},
    ("moe_dispatch", "bf16", "tpu"): {"block": (8, 8), "bn": 512,
                                      "min_bucket": 32, "nt": 2},
    ("moe_dispatch", "fp8", "tpu"): {"block": (8, 8), "bn": 512,
                                     "min_bucket": 32, "nt": 2},
    ("moe_dispatch", "f32", "cpu"): {"block": (8, 8), "bn": 128,
                                     "min_bucket": 8, "nt": 1},
    ("moe_dispatch", "bf16", "cpu"): {"block": (8, 8), "bn": 128,
                                      "min_bucket": 8, "nt": 1},
    ("moe_dispatch", "fp8", "cpu"): {"block": (8, 8), "bn": 128,
                                     "min_bucket": 8, "nt": 1},
    # WKV: the chunk length of the VMEM-resident-state recurrence kernel
    # (repro.kernels.wkv); longer chunks amortize the inter-chunk state
    # handoff, shorter ones bound the (chunk, chunk) intra-chunk attention
    # tile.  ops.wkv clamps to the (padded) sequence.
    ("wkv", "f32", "tpu"): {"chunk": 128},
    ("wkv", "bf16", "tpu"): {"chunk": 128},
    ("wkv", "fp8", "tpu"): {"chunk": 128},
    ("wkv", "f32", "cpu"): {"chunk": 128},
    ("wkv", "bf16", "cpu"): {"chunk": 128},
    ("wkv", "fp8", "cpu"): {"chunk": 128},
    # Flash attention: (bq, bk) query/key tile lengths.  Wider KV tiles cut
    # grid steps (fewer online-softmax rescales) until the double-buffered
    # (bk, D) K/V streams pressure VMEM; narrow dtypes afford wider tiles.
    # CPU rows keep the historical 128/128 (interpret mode, parity tests).
    ("flash", "f32", "tpu"): {"bq": 128, "bk": 256},
    ("flash", "bf16", "tpu"): {"bq": 128, "bk": 512},
    ("flash", "fp8", "tpu"): {"bq": 128, "bk": 512},
    ("flash", "f32", "cpu"): {"bq": 128, "bk": 128},
    ("flash", "bf16", "cpu"): {"bq": 128, "bk": 128},
    ("flash", "fp8", "cpu"): {"bq": 128, "bk": 128},
    # Block-sparse flash (BlockMask stream walk): narrower KV tiles than the
    # dense rows -- bk is also the mask's pattern resolution, so a narrower
    # tile walks fewer dead (q, k) pairs at the window/strided edges; sweeps
    # may register per-pattern overrides under "patterns": {name: {bq, bk}}.
    ("flash_sparse", "f32", "tpu"): {"bq": 128, "bk": 128},
    ("flash_sparse", "bf16", "tpu"): {"bq": 128, "bk": 256},
    ("flash_sparse", "fp8", "tpu"): {"bq": 128, "bk": 256},
    ("flash_sparse", "f32", "cpu"): {"bq": 128, "bk": 128},
    ("flash_sparse", "bf16", "cpu"): {"bq": 128, "bk": 128},
    ("flash_sparse", "fp8", "cpu"): {"bq": 128, "bk": 128},
    # Stencil: per-ndim halo tiles; minor dim pinned to the 128 lane width.
    ("stencil2d", "f32", "tpu"): {"tile": (256, 256)},
    ("stencil2d", "bf16", "tpu"): {"tile": (256, 512)},
    ("stencil2d", "fp8", "tpu"): {"tile": (256, 512)},
    ("stencil2d", "f32", "cpu"): {"tile": (64, 128)},
    ("stencil2d", "bf16", "cpu"): {"tile": (64, 128)},
    ("stencil2d", "fp8", "cpu"): {"tile": (64, 128)},
    ("stencil3d", "f32", "tpu"): {"tile": (8, 32, 256)},
    ("stencil3d", "bf16", "tpu"): {"tile": (8, 32, 512)},
    ("stencil3d", "fp8", "cpu"): {"tile": (8, 16, 128)},
    ("stencil3d", "f32", "cpu"): {"tile": (8, 16, 128)},
    ("stencil3d", "bf16", "cpu"): {"tile": (8, 16, 128)},
    ("stencil3d", "fp8", "tpu"): {"tile": (8, 32, 512)},
}


def register(op: str, dtype, params: Dict[str, Any], *, platform: str | None = None):
    """Override / extend a table row (e.g. from a measured on-device sweep)."""
    plat = platform or ("tpu" if on_tpu() else "cpu")
    _TABLE[(op, _bucket(dtype), plat)] = dict(params)


def _row(op: str, dtype) -> Dict[str, Any]:
    plat = "tpu" if on_tpu() else "cpu"
    key = (op, _bucket(dtype), plat)
    if key not in _TABLE:  # unknown bucket -> conservative f32/cpu row
        key = (op, "f32", "cpu")
    return dict(_TABLE[key])


# ---------------------------------------------------------------------------
# Per-op lookups (shape-aware clamping on top of the table row).
# ---------------------------------------------------------------------------

def _clamp_bn(bn: int, n: int, dtype, bk: int) -> int:
    """Clamp an SpMM-style N-tile: no wider than N rounded up to the lane
    width (a tile wider than the whole operand is pure padding), then halved
    while the (bk, bn) dense tile + (8, bn) f32 accumulator, double-buffered,
    would exceed the VMEM budget."""
    n_aligned = -(-max(n, 1) // LANE) * LANE
    bn = min(bn, max(LANE, n_aligned))
    while bn > LANE and 2 * (bk * bn * _dtype_bytes(dtype) + SUBLANE * bn * 4) > VMEM_BUDGET:
        bn //= 2
    return bn


def _clamp_nt(nt: int, bn: int, n: int, dtype, bk: int) -> int:
    """Clamp the SpMM output-residency width: the (bm-sublane, nt*bn) f32
    accumulator plus the double-buffered (bk, bn) dense stream must fit the
    VMEM budget, and a supertile wider than the whole (lane-aligned) operand
    is pure padding."""
    nt = max(1, int(nt))
    n_aligned = -(-max(n, 1) // LANE) * LANE
    while nt > 1 and (nt - 1) * bn >= n_aligned:
        nt //= 2
    while nt > 1 and (2 * bk * bn * _dtype_bytes(dtype)
                      + 2 * SUBLANE * nt * bn * 4) > VMEM_BUDGET:
        nt //= 2
    return nt


def spmm_bn(n: int, dtype=jnp.float32, *, bk: int = 8) -> int:
    """N-tile for the BCSR SpMM kernel (table row + shape/VMEM clamp)."""
    return _clamp_bn(int(_row("spmm", dtype)["bn"]), n, dtype, bk)


def spmm_tiles(n: int, dtype=jnp.float32, *, bk: int = 8) -> Dict[str, int]:
    """{"bn", "nt"} for the BCSR SpMM kernel: the N-tile plus the
    output-residency width (how many N-tiles stay VMEM-resident per walk of
    the index/block stream), both shape/VMEM clamped."""
    row = _row("spmm", dtype)
    bn = _clamp_bn(int(row["bn"]), n, dtype, bk)
    return {"bn": bn, "nt": _clamp_nt(int(row.get("nt", 1)), bn, n, dtype, bk)}


def spmspm_tiles(r: int, c: int, la: int, lb: int, dtype=jnp.float32
                 ) -> Tuple[int, int]:
    """(rt, ct) accumulator tile for the all-pairs intersection kernel."""
    row = _row("spmspm", dtype)
    rt, ct = int(row["rt"]), int(row["ct"])
    # Never tile wider than the (padded) problem.
    rt = min(rt, -(-max(r, 1) // SUBLANE) * SUBLANE)
    ct = min(ct, -(-max(c, 1) // SUBLANE) * SUBLANE)
    # Stream working set: (rt, la) + (ct, lb) keys+vals, int32+f32.
    while rt > SUBLANE and 8 * (rt * la + ct * lb) > VMEM_BUDGET:
        rt = max(SUBLANE, rt // 2)
        ct = max(SUBLANE, ct // 2)
    return rt, ct


def spmspm_nt(c: int, ct: int, lb: int, dtype=jnp.float32) -> int:
    """Output-column residency width for the intersection kernel: one step
    computes (rt, nt*ct) outputs from an (nt*ct, lb) B-stream block, so the
    A row stream is walked once per ``nt`` column tiles.  Clamped so the
    wider B block stays within the stream working-set budget."""
    nt = max(1, int(_row("spmspm", dtype).get("nt", 1)))
    c_aligned = -(-max(c, 1) // SUBLANE) * SUBLANE
    while nt > 1 and (nt - 1) * ct >= c_aligned:
        nt //= 2
    while nt > 1 and 8 * nt * ct * lb > VMEM_BUDGET:
        nt //= 2
    return nt


def moe_dispatch_tiles(d_model: int, dtype=jnp.float32) -> Dict[str, Any]:
    """{"block": (bm, bk), "bn": int, "min_bucket": int, "nt": int} for the
    MoE dispatch-as-SpMM path; ``bn`` (the d_model N-tile of the token
    operand) gets the same shape/VMEM clamp as :func:`spmm_bn` and ``nt``
    the residency clamp of :func:`spmm_tiles`; ``min_bucket`` feeds
    ``engine.stream_bucket`` when a routed stream is bucketed for a
    compiled step (rows registered without it fall back to 8)."""
    row = _row("moe_dispatch", dtype)
    bm, bk = row["block"]
    bn = _clamp_bn(int(row["bn"]), d_model, dtype, bk)
    return {"block": (int(bm), int(bk)), "bn": bn,
            "min_bucket": int(row.get("min_bucket", 8)),
            "nt": _clamp_nt(int(row.get("nt", 1)), bn, d_model, dtype, bk)}


def wkv_chunk(t: int, dtype=jnp.float32) -> int:
    """Chunk length for the WKV recurrence kernel, clamped to the sequence
    (the historical ``min(chunk, max(8, T))`` contract)."""
    return min(int(_row("wkv", dtype)["chunk"]), max(SUBLANE, int(t)))


def flash_tiles(sq: int, skv: int, d: int, dtype=jnp.float32
                ) -> Tuple[int, int]:
    """(bq, bk) tile lengths for the flash-attention kernel: no longer than
    the (sublane-aligned) sequences, and bk halves while the double-buffered
    K+V streams plus the f32 accumulator/softmax state would exceed the
    VMEM budget (ops applies its divisibility-aware re-clamp on top)."""
    row = _row("flash", dtype)
    bq, bk = int(row["bq"]), int(row["bk"])
    bq = min(bq, -(-max(sq, 1) // SUBLANE) * SUBLANE)
    bk = min(bk, -(-max(skv, 1) // SUBLANE) * SUBLANE)
    eb = _dtype_bytes(dtype)
    while bk > LANE and (4 * bk * d * eb + bq * d * 4
                         + 2 * bq * d * eb) > VMEM_BUDGET:
        bk //= 2
    return bq, bk


def flash_sparse_tiles(sq: int, skv: int, d: int, dtype=jnp.float32, *,
                       pattern: str | None = None) -> Tuple[int, int]:
    """(bq, bk) for the block-sparse flash kernel.  The table row may carry
    per-pattern overrides (``"patterns": {"window": {"bq", "bk"}, ...}``,
    registered by ``benchmarks/sweep_tiles.py``); shape/VMEM clamping matches
    :func:`flash_tiles`."""
    row = _row("flash_sparse", dtype)
    if not row:  # missing fallback row -> share the dense flash defaults
        row = _row("flash", dtype)
    params = dict(row)
    if pattern is not None:
        params.update(row.get("patterns", {}).get(pattern, {}))
    bq, bk = int(params["bq"]), int(params["bk"])
    bq = min(bq, -(-max(sq, 1) // SUBLANE) * SUBLANE)
    bk = min(bk, -(-max(skv, 1) // SUBLANE) * SUBLANE)
    eb = _dtype_bytes(dtype)
    while bk > LANE and (4 * bk * d * eb + bq * d * 4
                         + 2 * bq * d * eb) > VMEM_BUDGET:
        bk //= 2
    return bq, bk


def stencil_tile(interior: Tuple[int, ...], dtype=jnp.float32) -> Tuple[int, ...]:
    """Halo-tile for the 2-D/3-D stencil kernels (minor dim lane-aligned)."""
    ndim = len(interior)
    tile = tuple(_row(f"stencil{ndim}d", dtype)["tile"])
    # Clamp each dim to the interior rounded up to its alignment quantum
    # (8 for majors, 128 for the minor) -- ops.apply re-clamps identically,
    # so the table only ever *suggests*.
    out = []
    for i, (t, n) in enumerate(zip(tile, interior)):
        q = LANE if i == ndim - 1 else SUBLANE
        out.append(min(t, -(-max(n, 1) // q) * q))
    return tuple(out)


def lookup(op: str, *, dtype=jnp.float32, **shape) -> Dict[str, Any]:
    """Generic front door used by benchmarks / diagnostics."""
    if op == "spmm":
        return spmm_tiles(shape.get("n", LANE), dtype,
                          bk=shape.get("bk", SUBLANE))
    if op == "spmspm":
        rt, ct = spmspm_tiles(shape.get("r", SUBLANE), shape.get("c", SUBLANE),
                              shape.get("la", 1), shape.get("lb", 1), dtype)
        return {"rt": rt, "ct": ct,
                "nt": spmspm_nt(shape.get("c", SUBLANE), ct,
                                shape.get("lb", 1), dtype)}
    if op == "moe_dispatch":
        return moe_dispatch_tiles(shape.get("d_model", LANE), dtype)
    if op == "wkv":
        return {"chunk": wkv_chunk(shape.get("t", LANE), dtype)}
    if op == "flash":
        bq, bk = flash_tiles(shape.get("sq", LANE), shape.get("skv", LANE),
                             shape.get("d", LANE), dtype)
        return {"bq": bq, "bk": bk}
    if op == "flash_sparse":
        bq, bk = flash_sparse_tiles(shape.get("sq", LANE),
                                    shape.get("skv", LANE),
                                    shape.get("d", LANE), dtype,
                                    pattern=shape.get("pattern"))
        return {"bq": bq, "bk": bk}
    if op == "stencil":
        return {"tile": stencil_tile(shape["interior"], dtype)}
    raise KeyError(f"unknown op {op!r}")
