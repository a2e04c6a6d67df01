"""Sparse showcase: the paper's three workloads on the core library +
Pallas kernels (interpret mode on CPU).

  1. stencil (Fig. 6a): j3d27pt through the halo-overlapped Pallas kernel
  2. SpMM (Fig. 6b): BCSR index stream driving the scalar-prefetch kernel
  3. SpMSpM (Fig. 6c): sorted-stream intersection + GCOMP accounting
  4. SU union: sparse gradient exchange primitive
  5. sharded + batched engine: the "48 clusters" layer -- the same kernels
     shard_map-partitioned over a virtual-device mesh, bit-for-bit equal

Run:  PYTHONPATH=src python examples/sparse_showcase.py
"""
from repro.kernels.engine import ensure_virtual_devices

ensure_virtual_devices(4)  # before the first jax backend touch

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (STENCILS, banded_sparse, bcsr_from_dense,
                        intersect, random_dense_sparse, topk_sparsify,
                        union_add)
from repro.core.formats import INVALID_KEY
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmm.ref import spmm_ref
from repro.kernels.spmspm import ops as spmspm_ops
from repro.kernels.spmspm.ref import spmspm_ref
from repro.kernels.stencil import ops as stencil_ops
from repro.kernels.stencil.ref import stencil_ref

rng = np.random.default_rng(0)

# 1 -- stencil
spec = STENCILS["j3d27pt"]
grid = jnp.asarray(rng.standard_normal((18, 24, 136)), jnp.float32)
out = stencil_ops.apply(grid, spec, tile=(4, 8, 128), interpret=True)
ref = stencil_ref(grid, spec)
print(f"[stencil j3d27pt] out {out.shape}, max|err| vs oracle: "
      f"{float(jnp.abs(out - ref).max()):.2e}, "
      f"flops={stencil_ops.flops(spec, out.shape):,}")

# 2 -- SpMM on the Pallas kernel (block index stream -> DMA steering)
a_dense = banded_sparse(rng, (128, 128), bandwidth=10)
a = bcsr_from_dense(a_dense, (8, 8))
b = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
c = spmm_ops.spmm(a, b, interpret=True)
print(f"[spmm banded] nnzb={a.nnzb} block_density={a.density():.3f}, "
      f"max|err|: {float(jnp.abs(c - spmm_ref(a, b)).max()):.2e}")

# 3 -- SpMSpM: intersection kernel + index-comparison-rate accounting
left = random_dense_sparse(rng, (32, 256), 0.15)
right = random_dense_sparse(rng, (256, 32), 0.01)   # paper's 1% density
ak, av = spmspm_ops.dense_to_ell_rows(left)
bk, bv = spmspm_ops.dense_to_ell_cols(right)
cc = spmspm_ops.spmspm(ak, av, bk, bv, interpret=True)
st = spmspm_ops.comparison_stats(ak, bk)
print(f"[spmspm 1%] max|err|: "
      f"{float(jnp.abs(cc - spmspm_ref(ak, av, bk, bv, 256)).max()):.2e}, "
      f"comparisons issued={st['issued']:,} useful<={st['useful_upper']}")

# 4 -- SU stream ops: intersect / union (the comparator modes)
ka = jnp.asarray(np.sort(rng.choice(1000, 64, replace=False)).astype(np.int32))
kb = jnp.asarray(np.sort(rng.choice(1000, 96, replace=False)).astype(np.int32))
kb = jnp.pad(kb, (0, 32), constant_values=INVALID_KEY)
ka = jnp.pad(ka, (0, 64), constant_values=INVALID_KEY)
res = intersect(ka, kb)
print(f"[SU intersect] |A|=64 |B|=96 -> {int(res.count)} matches "
      f"(np.intersect1d agrees: "
      f"{np.array_equal(np.asarray(res.keys[:int(res.count)]), np.intersect1d(np.asarray(ka[:64]), np.asarray(kb[:96])))})")

g = jnp.asarray(rng.standard_normal(512), jnp.float32)
keys, vals = topk_sparsify(g, 32)
u = union_add(keys, vals, keys, vals)
print(f"[SU union] top-32 grad stream unioned with itself -> "
      f"{int(u.count)} keys, values doubled: "
      f"{bool(jnp.allclose(u.values[:32], 2 * vals[jnp.argsort(keys)]))}")

# 5 -- the sharded + batched engine (the multi-cluster layer)
from repro.core.formats import batched_bcsr_from_dense
from repro.kernels import engine
from repro.parallel.mesh import make_mesh

mesh = make_mesh((jax.device_count(),), ("data",))
c_sh = engine.shard_spmm(a, b, mesh=mesh)
print(f"[engine shard_spmm x{jax.device_count()}] bit-for-bit vs 1-device: "
      f"{bool((np.asarray(c_sh) == np.asarray(c)).all())}")

stack = np.stack([random_dense_sparse(rng, (64, 64), 0.15) for _ in range(4)])
ab = batched_bcsr_from_dense(stack, (8, 8))
db = jnp.asarray(rng.standard_normal((4, 64, 96)), jnp.float32)
cb = engine.shard_spmm_batched(ab, db, mesh=mesh)
print(f"[engine batched x4 matrices] union-stream nnzb={ab.nnzb} "
      f"out={cb.shape}, max|err| vs per-matrix oracle: "
      f"{max(float(jnp.abs(cb[i] - spmm_ref(ab[i], db[i])).max()) for i in range(4)):.2e}")

cs = engine.shard_spmspm(ak, av, bk, bv, mesh=mesh)
print(f"[engine shard_spmspm] bit-for-bit vs 1-device: "
      f"{bool((np.asarray(cs) == np.asarray(cc)).all())}")
