"""Multi-tile output residency (``nt``): the tier-1 parity suite.

Contract (tests/README.md "Residency & overlap contract"): widening the
VMEM-resident accumulator to ``nt`` N-tiles changes ONLY how often the
index/block stream is re-walked -- never a single output bit.  Per output
element the accumulation order is the stream order for any ``nt``, so every
test here uses ``assert_array_equal`` against ``nt=1``, including ragged
``N % (nt*bn) != 0`` shapes, the trace-safe bucketed stream entry, and the
sharded engine wrappers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import (batched_bcsr_from_dense, bcsr_from_dense,
                                random_dense_sparse)
from repro.kernels import engine, tuning
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmm.kernel import stream_walks
from repro.kernels.spmm.ref import spmm_ref
from repro.kernels.spmspm import ops as spmspm_ops
from repro.kernels.spmspm.ref import spmspm_ref
from repro.parallel.mesh import make_mesh

RNG = np.random.default_rng(11)


def _mesh(n):
    return make_mesh((n,), ("data",))


# ---------------------------------------------------------------------------
# spmm_bcsr: nt-wide accumulator
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nt", [2, 4])
@pytest.mark.parametrize("N", [512, 500, 130])  # incl. N % (nt*bn) != 0
def test_spmm_nt_bit_identical(nt, N):
    a_dense = random_dense_sparse(RNG, (128, 96), 0.2)
    a = bcsr_from_dense(a_dense, (8, 8))
    b = jnp.asarray(RNG.standard_normal((96, N)), jnp.float32)
    want = spmm_ops.spmm(a, b, bn=128, nt=1, interpret=True)
    got = spmm_ops.spmm(a, b, bn=128, nt=nt, interpret=True)
    assert got.shape == (128, N)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got), np.asarray(spmm_ref(a, b)),
                               atol=1e-4, rtol=1e-4)


def test_spmm_nt_empty_rows_and_batched():
    """Row-coverage padding and the vmapped batched kernel hold under nt."""
    a_dense = np.zeros((64, 64), np.float32)
    a_dense[9, :16] = 1.0
    a = bcsr_from_dense(a_dense, (8, 8))
    b = jnp.asarray(RNG.standard_normal((64, 256)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(spmm_ops.spmm(a, b, bn=128, nt=2, interpret=True)),
        np.asarray(spmm_ops.spmm(a, b, bn=128, nt=1, interpret=True)))

    stack = np.stack([random_dense_sparse(RNG, (64, 64), 0.15)
                      for _ in range(3)])
    ab = batched_bcsr_from_dense(stack, (8, 8))
    d = jnp.asarray(RNG.standard_normal((3, 64, 384)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(spmm_ops.spmm_batched(ab, d, bn=128, nt=2,
                                         interpret=True)),
        np.asarray(spmm_ops.spmm_batched(ab, d, bn=128, nt=1,
                                         interpret=True)))


def test_spmm_nt_validation_and_walks():
    a = bcsr_from_dense(random_dense_sparse(RNG, (32, 32), 0.4), (8, 8))
    b = jnp.asarray(RNG.standard_normal((32, 128)), jnp.float32)
    with pytest.raises(ValueError, match="nt=0"):
        spmm_ops.spmm(a, b, nt=0, interpret=True)
    ak, av = spmspm_ops.dense_to_ell_rows(np.eye(8, dtype=np.float32))
    with pytest.raises(ValueError, match="nt=0"):
        spmspm_ops.spmspm(ak, av, ak, av, rt=8, ct=8, nt=0, interpret=True)
    # the reread invariant the benchmarks report
    assert stream_walks(512, 128, 1) == 4
    assert stream_walks(512, 128, 4) == 1
    assert stream_walks(500, 128, 2) == 2


def test_tuning_nt_clamps():
    """The table's nt clamps to the operand: a supertile wider than N is
    pure padding; CPU rows pin nt=1."""
    t = tuning.spmm_tiles(1024, jnp.float32)
    assert t["nt"] >= 1 and t["bn"] >= tuning.LANE
    assert tuning.spmm_tiles(128, jnp.float32)["nt"] == 1  # one tile fits all
    assert tuning.moe_dispatch_tiles(64, jnp.float32)["nt"] == 1
    assert tuning.spmspm_nt(8, 8, 4, jnp.float32) == 1


# ---------------------------------------------------------------------------
# sharded engine wrappers
# ---------------------------------------------------------------------------

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 2, reason="needs a >=2-device mesh "
    "(set XLA_FLAGS=--xla_force_host_platform_device_count=4)")


@needs_mesh
@pytest.mark.parametrize("N", [512, 320])
def test_shard_spmm_nt_matches_single_device(N):
    a = bcsr_from_dense(random_dense_sparse(RNG, (64, 64), 0.2), (8, 8))
    b = jnp.asarray(RNG.standard_normal((64, N)), jnp.float32)
    want = spmm_ops.spmm(a, b, bn=128, nt=1, interpret=True)
    got = engine.shard_spmm(a, b, mesh=_mesh(2), bn=128, nt=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs_mesh
def test_shard_spmm_batched_stream_nt_trace_safe():
    """The phase-2 entry stays trace-safe with a widened accumulator, and
    the bucketed wrapper threads nt through."""
    stack = np.stack([random_dense_sparse(RNG, (32, 32), 0.3)
                      for _ in range(2)])
    a = spmm_ops.pad_empty_rows(batched_bcsr_from_dense(stack, (8, 8)))
    d = jnp.asarray(RNG.standard_normal((2, 32, 256)), jnp.float32)
    want = engine.shard_spmm_batched(a, d, mesh=_mesh(2), bn=128, nt=1)
    fn = jax.jit(lambda a, d: engine.shard_spmm_batched_stream(
        a, d, mesh=_mesh(2), bn=128, nt=2))
    np.testing.assert_array_equal(np.asarray(fn(a, d)), np.asarray(want))
    got_b = engine.shard_spmm_batched_bucketed(a, d, mesh=_mesh(2), bn=128,
                                               nt=2)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want))


# ---------------------------------------------------------------------------
# spmspm: multi-output-column residency
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nt", [2, 4])
@pytest.mark.parametrize("C", [64, 52])  # incl. C % (nt*ct) != 0
def test_spmspm_nt_bit_identical(nt, C):
    left = random_dense_sparse(RNG, (48, 256), 0.1)
    right = random_dense_sparse(RNG, (256, C), 0.05)
    ak, av = spmspm_ops.dense_to_ell_rows(left)
    bk, bv = spmspm_ops.dense_to_ell_cols(right)
    want = spmspm_ops.spmspm(ak, av, bk, bv, rt=8, ct=8, nt=1,
                             interpret=True)
    got = spmspm_ops.spmspm(ak, av, bk, bv, rt=8, ct=8, nt=nt,
                            interpret=True)
    assert got.shape == (48, C)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(spmspm_ref(ak, av, bk, bv, 256)),
        atol=1e-4, rtol=1e-4)


@needs_mesh
def test_shard_spmspm_nt_matches_single_device():
    left = random_dense_sparse(RNG, (32, 128), 0.1)
    right = random_dense_sparse(RNG, (128, 40), 0.05)
    ak, av = spmspm_ops.dense_to_ell_rows(left)
    bk, bv = spmspm_ops.dense_to_ell_cols(right)
    want = spmspm_ops.spmspm(ak, av, bk, bv, rt=8, ct=8, nt=1,
                             interpret=True)
    got = engine.shard_spmspm(ak, av, bk, bv, mesh=_mesh(2), rt=8, ct=8,
                              nt=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
