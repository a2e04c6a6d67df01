"""Measured autotune sweep: (bn, nt) and flash (bq, bk) on real shapes,
winners registered into the tile table.

ROADMAP "measured, not heuristic, autotune rows": the static table in
``repro.kernels.tuning`` was sized from the roofline model; this harness
*measures* the candidate grid on the attached backend and calls
``tuning.register`` with the winners, so a process that runs the sweep first
serves every later kernel call from measured rows.  Artifacts go to
``BENCH_sweep_tiles.json`` (every point, not just winners -- the losing
points are the record of *why* the winner won).

Axes swept per op:
  * ``spmm``          -- bn x nt (dense N-tile x output-residency width) on a
    block-uniform BCSR x dense of the benchmark shapes.  The structural
    stream-walk count rides along with each timing: on interpret-mode CPU
    the wall clock is emulation-dominated, so the winner is chosen by
    (walks, time) lexicographically on TPU and time-only on CPU.
  * ``flash``         -- (bq, bk) on a causal prefill shape, dense grid and
    the block-sparse sliding-window walk.  The sparse rows carry their
    walked-tile counts (bk is also the mask's pattern resolution: narrower
    KV tiles prune the window edge tighter but walk a longer stream); the
    winner registers the base ``flash_sparse`` row plus a per-pattern
    ``{"patterns": {"window": ...}}`` override (``tuning
    .flash_sparse_tiles``).

Run modes:
  python benchmarks/sweep_tiles.py                 # full sweep + register
  python benchmarks/sweep_tiles.py --smoke         # one tiny point per op
                                                   # (the CI bit-rot guard)
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit_bench, row, time_fn
from repro.core.formats import bcsr_from_dense
from repro.kernels import tuning
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmm.kernel import stream_walks


def _block_uniform(rng, shape, density, block=(8, 8)):
    gm, gn = shape[0] // block[0], shape[1] // block[1]
    mask = np.kron(rng.random((gm, gn)) < density, np.ones(block, bool))
    return np.where(mask, rng.standard_normal(shape), 0).astype(np.float32)


def sweep_spmm(*, smoke: bool = False, register: bool = True) -> dict:
    """Sweep (bn, nt) for the BCSR SpMM kernel; returns the point table and
    (optionally) registers the winner for the current platform."""
    rng = np.random.default_rng(0)
    if smoke:
        M_, K_, N_ = 64, 64, 256
        bns, nts = (128,), (1, 2)
    else:
        M_, K_, N_ = 1024, 1024, 1024
        bns, nts = (128, 256, 512), (1, 2, 4, 8)
    a = bcsr_from_dense(_block_uniform(rng, (M_, K_), 0.05), (8, 8))
    b = jnp.asarray(rng.standard_normal((K_, N_)), jnp.float32)
    interpret = not tuning.on_tpu()

    points = []
    ref = np.asarray(spmm_ops.spmm(a, b, bn=bns[0], nt=1,
                                   interpret=interpret))
    for bn in bns:
        if bn > N_:
            continue
        for nt in nts:
            if nt * bn > N_:
                continue
            t = time_fn(lambda bn=bn, nt=nt: spmm_ops.spmm(
                a, b, bn=bn, nt=nt, interpret=interpret))
            out = np.asarray(spmm_ops.spmm(a, b, bn=bn, nt=nt,
                                           interpret=interpret))
            points.append({"bn": bn, "nt": nt, "t_us": t * 1e6,
                           "stream_walks": stream_walks(N_, bn, nt),
                           "bit_identical": bool((out == ref).all())})
    assert all(p["bit_identical"] for p in points), "sweep found divergence"
    # TPU: fewer stream walks first (the HBM term), wall time second;
    # interpret-mode CPU: wall time only (walks measure nothing there).
    key = ((lambda p: (p["stream_walks"], p["t_us"])) if tuning.on_tpu()
           else (lambda p: p["t_us"]))
    best = min(points, key=key)
    if register:
        tuning.register("spmm", jnp.float32,
                        {"bn": best["bn"], "nt": best["nt"]})
    return {"shape": {"M": M_, "K": K_, "N": N_, "nnzb": int(a.nnzb)},
            "points": points, "winner": best, "registered": bool(register)}


def sweep_flash(*, smoke: bool = False, register: bool = True) -> dict:
    """Sweep flash-attention (bq, bk) on a causal prefill shape: the dense
    full-grid kernel and the block-sparse sliding-window walk, every point
    parity-checked against the bq/bk-independent jnp oracle.  Winners go to
    the ``"flash"`` row and the ``"flash_sparse"`` row (base + a
    ``"patterns": {"window": ...}`` override -- the sparse walk may prefer a
    different KV tile than the dense grid, since bk doubles as the mask's
    pattern resolution)."""
    from repro.core.masks import BlockMask
    from repro.kernels.flash_attention import ops as fops
    from repro.kernels.flash_attention.ref import attention_ref

    rng = jax.random.split(jax.random.PRNGKey(0), 3)
    if smoke:
        B, H, S, D = 1, 1, 64, 16
        tiles = ((16, 16), (16, 32))
    else:
        B, H, S, D = 1, 2, 1024, 64
        tiles = ((64, 64), (64, 128), (128, 128), (128, 256))
    window = S // 4
    q = jax.random.normal(rng[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(rng[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(rng[2], (B, H, S, D), jnp.float32)
    interpret = not tuning.on_tpu()
    ref = np.asarray(attention_ref(q, k, v, causal=True, window=window))

    dense_pts, sparse_pts = [], []
    for bq, bk in tiles:
        t_d = time_fn(lambda bq=bq, bk=bk: fops.attention(
            q, k, v, causal=True, window=window, bq=bq, bk=bk,
            interpret=interpret), warmup=1, iters=3)
        out = np.asarray(fops.attention(q, k, v, causal=True, window=window,
                                        bq=bq, bk=bk, interpret=interpret))
        ok = bool(np.allclose(out, ref, atol=2e-3, rtol=2e-3))
        dense_pts.append({"bq": bq, "bk": bk, "t_us": t_d * 1e6,
                          "tiles": (S // bq) * (S // bk), "parity": ok})

        mask = BlockMask.sliding_window(S, S, window, bq=bq, bk=bk)
        t_s = time_fn(lambda q=q, mask=mask: fops.attention(
            q, k, v, mask=mask, mask_impl="sparse", interpret=interpret),
            warmup=1, iters=3)
        outs = np.asarray(fops.attention(q, k, v, mask=mask,
                                         mask_impl="sparse",
                                         interpret=interpret))
        oks = bool(np.allclose(outs, ref, atol=2e-3, rtol=2e-3))
        sparse_pts.append({"bq": bq, "bk": bk, "t_us": t_s * 1e6,
                           "walked_tiles": mask.lower(bucket=True).capacity,
                           "parity": oks})
    assert all(p["parity"] for p in dense_pts + sparse_pts), \
        "flash sweep found divergence from the oracle"
    # TPU scores structure first (walked tiles ~ HBM traffic), CPU time only
    # (interpret emulation swamps the stream contrast at sweep shapes).
    d_key = ((lambda p: (p["tiles"], p["t_us"])) if tuning.on_tpu()
             else (lambda p: p["t_us"]))
    s_key = ((lambda p: (p["walked_tiles"], p["t_us"])) if tuning.on_tpu()
             else (lambda p: p["t_us"]))
    best_d = min(dense_pts, key=d_key)
    best_s = min(sparse_pts, key=s_key)
    if register:
        tuning.register("flash", jnp.float32,
                        {"bq": best_d["bq"], "bk": best_d["bk"]})
        base = tuning._row("flash_sparse", jnp.float32)
        tuning.register("flash_sparse", jnp.float32, {
            "bq": base["bq"], "bk": base["bk"],
            "patterns": {**base.get("patterns", {}),
                         "window": {"bq": best_s["bq"], "bk": best_s["bk"]}}})
    return {"shape": {"B": B, "H": H, "S": S, "D": D, "window": window},
            "dense_points": dense_pts, "sparse_points": sparse_pts,
            "points": dense_pts + sparse_pts,
            "winner": {**best_s, "dense_bq": best_d["bq"],
                       "dense_bk": best_d["bk"]},
            "registered": bool(register)}


def run(*, smoke: bool = False, register: bool = True) -> dict:
    return {"spmm": sweep_spmm(smoke=smoke, register=register),
            "flash": sweep_flash(smoke=smoke, register=register)}


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    results = run(smoke=smoke)
    rows = []
    for op, res in results.items():
        for p in res["points"]:
            detail = ";".join(f"{k}={v}" for k, v in p.items()
                              if k != "t_us")
            rows.append(row(f"sweep/{op}", p["t_us"], detail))
        rows.append(row(f"sweep/{op}/winner", res["winner"]["t_us"],
                        ";".join(f"{k}={v}" for k, v in res["winner"].items()
                                 if k != "t_us")))
    results["rows"] = rows
    results["smoke"] = smoke
    path = emit_bench("sweep_tiles", results)
    print("\n".join(rows))
    print(f"# wrote {path}")
