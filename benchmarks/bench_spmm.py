"""Paper Fig. 6b: sparse-dense matrix multiply (SpMM) with / without SUs.

Three variants, mirroring the paper's axes:
* ``su_bcsr``  -- the SU formulation: the block-column index stream drives
  block gathers of the dense operand + back-to-back block GEMMs (what the
  Pallas kernel executes tile-wise on TPU).
* ``noSU_csr`` -- the scalar-ISA analogue: element-granular CSR with one
  explicit gather per nonzero + segment-sum (address arithmetic in code).
* ``dense``    -- dense GEMM reference (utilization denominator).

The paper's matrices are SuiteSparse; offline stand-ins sweep the same
structure axes (uniform / banded / power-law). FoMs: useful GFLOP/s,
+/-SU speedup (paper: 4.6x), utilization vs dense peak (paper: 42%).
Run modes (``python benchmarks/bench_spmm.py [--shard] [--batched]``):
* default     -- single-device variants below.
* ``--shard``   -- the sharded engine (repro.kernels.engine) on a 1-D mesh
  of virtual CPU devices (or real devices when present): N-partitioned
  SpMM + column-partitioned SpMSpM, vs. their single-device twins.
* ``--batched`` -- BatchedBCSR x dense through the vmapped kernel vs. a
  python loop over per-matrix calls (the dispatch-overhead contrast).
"""
from __future__ import annotations

import sys

if __name__ == "__main__" and "--shard" in sys.argv:
    # Must precede the first jax backend touch: fake a 4-device host.
    from repro.kernels.engine import ensure_virtual_devices
    ensure_virtual_devices(4)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import PEAK_FLOPS, emit_bench, row, time_fn
from repro.core.formats import (banded_sparse, bcsr_from_dense, csr_from_dense,
                                powerlaw_sparse, random_dense_sparse)
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmm.kernel import stream_walks

M, K, N = 1024, 1024, 512


def _block_uniform(rng, shape, density, block=(8, 8)):
    """Uniform sparsity at BLOCK granularity: the structured case the TPU
    re-blocking (DESIGN.md S2.2) is built for."""
    gm, gn = shape[0] // block[0], shape[1] // block[1]
    mask = np.kron(rng.random((gm, gn)) < density,
                   np.ones(block, bool))
    return np.where(mask, rng.standard_normal(shape), 0).astype(np.float32)


import numpy as np  # noqa: E402  (used by _block_uniform)

CASES = [
    ("uniform_1pct", lambda rng: random_dense_sparse(rng, (M, K), 0.01)),
    ("uniform_5pct", lambda rng: random_dense_sparse(rng, (M, K), 0.05)),
    ("blockuniform_5pct", lambda rng: _block_uniform(rng, (M, K), 0.05)),
    ("blockuniform_20pct", lambda rng: _block_uniform(rng, (M, K), 0.20)),
    ("banded_bw16", lambda rng: banded_sparse(rng, (M, K), 16)),
    ("powerlaw_5pct", lambda rng: powerlaw_sparse(rng, (M, K), 0.05)),
]


@jax.jit
def _su_bcsr(block_rows, block_cols, blocks, b):
    """Block index stream -> gather dense K-tiles -> batched GEMM -> scatter."""
    nnzb, bm, bk = blocks.shape
    K_, N_ = b.shape
    tiles = b.reshape(K_ // bk, bk, N_)
    gathered = jnp.take(tiles, block_cols, axis=0)            # SU indirection
    partial = jnp.einsum("zmk,zkn->zmn", blocks, gathered,
                         preferred_element_type=jnp.float32)
    out = jnp.zeros((M // bm, bm, N_), jnp.float32)
    return out.at[block_rows].add(partial).reshape(M, N_)


@jax.jit
def _nosu_csr(indptr, indices, values, b):
    """Element-granular gather + segment-sum (the scalar-code analogue)."""
    rows = jnp.repeat(jnp.arange(M, dtype=jnp.int32), jnp.diff(indptr),
                      total_repeat_length=indices.shape[0])
    gathered = jnp.take(b, indices, axis=0) * values[:, None]
    return jnp.zeros((M, b.shape[1]), jnp.float32).at[rows].add(gathered)


@jax.jit
def _dense(a, b):
    return a @ b


def run_sharded() -> list:
    """--shard: the sharded engine end-to-end on an n-device mesh."""
    from repro.core.formats import batched_bcsr_from_dense
    from repro.kernels import engine
    from repro.parallel.mesh import make_mesh

    rng = np.random.default_rng(0)
    rows = []
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",))
    # Interpret-mode kernels pay a large per-grid-step emulation cost on
    # CPU, so the sharded demo runs reduced shapes; relative numbers (and
    # the end-to-end engine path) are what this mode exercises.
    Ms, Ks, Ns = 256, 256, 512
    b = jnp.asarray(rng.standard_normal((Ks, Ns)), jnp.float32)

    shard_cases = [
        ("blockuniform_5pct", _block_uniform(rng, (Ms, Ks), 0.05)),
        ("banded_bw16", banded_sparse(rng, (Ms, Ks), 16)),
    ]
    for name, a_dense in shard_cases:
        a = bcsr_from_dense(a_dense, (8, 8))
        t_one = time_fn(lambda: spmm_ops.spmm(a, b, bn=128, interpret=True))
        t_shard = time_fn(lambda: engine.shard_spmm(a, b, mesh=mesh))
        useful = spmm_ops.flops(a, Ns)
        rows.append(row(
            f"spmm/{name}/sharded_x{n_dev}", t_shard * 1e6,
            f"useful_gflops={useful / t_shard / 1e9:.2f};"
            f"speedup_vs_1dev={t_one / t_shard:.2f}x;devices={n_dev}"))

    # Batched MoE-style dispatch: 8 expert matrices, one token block.
    stack = np.stack([_block_uniform(rng, (256, 256), 0.05)
                      for _ in range(8)])
    ab = batched_bcsr_from_dense(stack, (8, 8))
    db = jnp.asarray(rng.standard_normal((8, 256, 256)), jnp.float32)
    t_b = time_fn(lambda: engine.shard_spmm_batched(ab, db, mesh=mesh))
    rows.append(row(f"spmm/batched8_sharded_x{n_dev}", t_b * 1e6,
                    f"useful_flops={spmm_ops.flops(ab, 256)};"
                    f"block_density={ab.density():.3f}"))

    # Sharded SpMSpM (column-partitioned B streams).
    from repro.kernels.spmspm import ops as spmspm_ops
    left = random_dense_sparse(rng, (64, 512), 0.1)
    right = random_dense_sparse(rng, (512, 64), 0.01)
    ak, av = spmspm_ops.dense_to_ell_rows(left)
    bk, bv = spmspm_ops.dense_to_ell_cols(right)
    t_ss = time_fn(lambda: engine.shard_spmspm(ak, av, bk, bv, mesh=mesh))
    rows.append(row(f"spmspm/sharded_x{n_dev}", t_ss * 1e6,
                    f"devices={n_dev}"))
    return rows


def run_batched() -> list:
    """--batched: vmapped batched kernel vs. a python loop of single calls."""
    from repro.core.formats import batched_bcsr_from_dense

    rng = np.random.default_rng(0)
    rows = []
    B = 8
    stack = np.stack([_block_uniform(rng, (256, 256), 0.05)
                      for _ in range(B)])
    a = batched_bcsr_from_dense(stack, (8, 8))
    d = jnp.asarray(rng.standard_normal((B, 256, 128)), jnp.float32)
    t_batched = time_fn(lambda: spmm_ops.spmm_batched(a, d, interpret=True))

    def looped():
        return [spmm_ops.spmm(a[i], d[i], interpret=True) for i in range(B)]

    t_loop = time_fn(looped)
    useful = spmm_ops.flops(a, 128)
    rows.append(row(f"spmm/batched{B}_vmap", t_batched * 1e6,
                    f"useful_flops={useful};"
                    f"speedup_vs_loop={t_loop / t_batched:.2f}x"))
    rows.append(row(f"spmm/batched{B}_loop", t_loop * 1e6, ""))
    return rows


def run_residency(bench_json: dict) -> list:
    """Multi-tile output residency: ``nt`` N-tiles of the output row stay
    VMEM-resident per walk of the index/block stream, so the stream reread
    factor drops from ``N/bn`` to ``N/(nt*bn)``.  Structural counts come
    from ``kernel.stream_walks`` (exact, backend-independent); wall times
    are interpret-mode (relative only).  Results feed BENCH_spmm.json."""
    rng = np.random.default_rng(0)
    rows = []
    bn = 128
    res_cases = [
        ("blockuniform_5pct", _block_uniform(rng, (M, K), 0.05)),
        ("banded_bw16", banded_sparse(rng, (M, K), 16)),
    ]
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    bench_json["residency"] = {"shapes": {"M": M, "K": K, "N": N,
                                          "block": [8, 8], "bn": bn},
                               "cases": {}}
    for name, a_dense in res_cases:
        a = bcsr_from_dense(a_dense, (8, 8))
        case = {"nnzb": int(a.nnzb)}
        ref = None
        for nt in (1, 2, 4):
            t = time_fn(lambda nt=nt: spmm_ops.spmm(a, b, bn=bn, nt=nt,
                                                    interpret=True))
            walks = stream_walks(N, bn, nt)
            out = np.asarray(spmm_ops.spmm(a, b, bn=bn, nt=nt,
                                           interpret=True))
            if ref is None:
                ref = out
            case[f"nt{nt}"] = {
                "t_us": t * 1e6,
                "stream_walks": walks,
                "stream_blocks_read": walks * int(a.nnzb),
                "bit_identical_to_nt1": bool((out == ref).all()),
            }
            rows.append(row(
                f"spmm/{name}/residency_nt{nt}", t * 1e6,
                f"stream_walks={walks};"
                f"reread_factor={walks};"
                f"bit_identical={(out == ref).all()}"))
        case["reread_reduction_nt4_vs_nt1"] = (
            case["nt1"]["stream_walks"] / case["nt4"]["stream_walks"])
        bench_json["residency"]["cases"][name] = case
    return rows


def run() -> list:
    rng = np.random.default_rng(0)
    rows = []
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    for name, gen in CASES:
        a_dense = gen(rng)
        a = bcsr_from_dense(a_dense, (8, 8))
        csr = csr_from_dense(a_dense)
        t_su = time_fn(_su_bcsr, a.block_rows, a.block_cols, a.blocks, b)
        t_nosu = time_fn(_nosu_csr, csr.indptr, csr.indices, csr.values, b)
        t_dense = time_fn(_dense, jnp.asarray(a_dense), b)
        useful = 2 * csr.nnz * N
        stream = spmm_ops.flops(a, N)  # includes block zero-padding work
        rows.append(row(
            f"spmm/{name}/su_bcsr", t_su * 1e6,
            f"useful_gflops={useful / t_su / 1e9:.2f};"
            f"speedup_vs_noSU={t_nosu / t_su:.2f}x;"
            f"block_density={a.density():.3f};"
            f"stream_efficiency={useful / max(stream, 1):.2f}"))
        rows.append(row(f"spmm/{name}/noSU_csr", t_nosu * 1e6,
                        f"useful_gflops={useful / t_nosu / 1e9:.2f}"))
        rows.append(row(f"spmm/{name}/dense", t_dense * 1e6,
                        f"gflops={2 * M * K * N / t_dense / 1e9:.2f};"
                        f"util_of_dense={(useful / t_su) / (2 * M * K * N / t_dense):.2f}"))
    return rows


if __name__ == "__main__":
    if "--shard" in sys.argv:
        print("\n".join(run_sharded()))
    elif "--batched" in sys.argv:
        print("\n".join(run_batched()))
    else:
        bench_json: dict = {}
        rows = run()
        rows += run_residency(bench_json)
        bench_json["rows"] = rows
        path = emit_bench("spmm", bench_json)
        print("\n".join(rows))
        print(f"# wrote {path}")
