"""Sharded + batched sparse engine vs. the single-device kernels.

Runs on a CPU mesh of virtual devices (conftest.py forces
``--xla_force_host_platform_device_count=4``).  The engine's contract is
*bit-for-bit* fp32 parity with the single-device kernel: every device runs
the identical Pallas program on identical operand values for its output
tiles, so not even accumulation order changes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import (batched_bcsr_from_dense, bcsr_from_dense,
                                powerlaw_sparse, random_dense_sparse)
from repro.kernels import engine
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmm.ref import spmm_ref
from repro.kernels.spmspm import ops as spmspm_ops
from repro.kernels.spmspm.ref import spmspm_ref
from repro.parallel.mesh import make_mesh

RNG = np.random.default_rng(42)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 2, reason="needs a >=2-device mesh "
    "(set XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _mesh(n):
    return make_mesh((n,), ("data",))


def test_mesh_has_virtual_devices():
    assert jax.device_count() >= 2


def test_ensure_virtual_devices_detects_late_call():
    """Once the backend is initialized the XLA_FLAGS override is inert:
    asking for more devices than exist must warn (raise under strict),
    not silently leave sharded tests on one device.  Asking for what we
    already have stays silent."""
    import warnings

    assert jax.local_device_count() >= 2  # backend is up (conftest: 4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        engine.ensure_virtual_devices(jax.local_device_count())
    with pytest.warns(RuntimeWarning, match="already initialized"):
        engine.ensure_virtual_devices(jax.local_device_count() + 64)
    with pytest.raises(RuntimeError, match="already initialized"):
        engine.ensure_virtual_devices(jax.local_device_count() + 64,
                                      strict=True)


# ---------------------------------------------------------------------------
# SpMM: N-partitioned
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev", [2, 4])
@pytest.mark.parametrize("N", [512, 256])
def test_shard_spmm_bitwise_matches_single_device(n_dev, N):
    a_dense = random_dense_sparse(RNG, (64, 64), 0.3)
    a = bcsr_from_dense(a_dense, (8, 8))
    b = jnp.asarray(RNG.standard_normal((64, N)), jnp.float32)
    got = engine.shard_spmm(a, b, mesh=_mesh(n_dev))
    want = spmm_ops.spmm(a, b, bn=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("N", [100, 300, 129])
def test_shard_spmm_uneven_n_tiles(N):
    """N not divisible by n_dev * bn: the engine pads and strips."""
    a_dense = random_dense_sparse(RNG, (32, 64), 0.4)
    a = bcsr_from_dense(a_dense, (8, 8))
    b = jnp.asarray(RNG.standard_normal((64, N)), jnp.float32)
    got = engine.shard_spmm(a, b, mesh=_mesh(4))
    assert got.shape == (32, N)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(spmm_ops.spmm(a, b, interpret=True)))


def test_shard_spmm_matches_oracle_powerlaw():
    """Sharded path against the densify-and-matmul oracle (not just the
    kernel), on a row-imbalanced matrix."""
    a_dense = powerlaw_sparse(RNG, (64, 64), 0.1)
    a = bcsr_from_dense(a_dense, (8, 8))
    b = jnp.asarray(RNG.standard_normal((64, 200)), jnp.float32)
    got = engine.shard_spmm(a, b, mesh=_mesh(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(spmm_ref(a, b)),
                               atol=1e-4, rtol=1e-4)


def test_shard_spmm_auto_mesh():
    """mesh=None resolves to a 1-D mesh over all local devices."""
    a = bcsr_from_dense(random_dense_sparse(RNG, (32, 32), 0.5), (8, 8))
    b = jnp.asarray(RNG.standard_normal((32, 256)), jnp.float32)
    got = engine.shard_spmm(a, b)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(spmm_ops.spmm(a, b, interpret=True)))


# ---------------------------------------------------------------------------
# Batched SpMM: batch-partitioned
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [4, 3, 6])  # 3 exercises the uneven-batch pad
def test_shard_spmm_batched_matches_per_matrix(B):
    stack = np.stack(
        [random_dense_sparse(RNG, (64, 64), 0.2) for _ in range(B)])
    a = batched_bcsr_from_dense(stack, (8, 8))
    d = jnp.asarray(RNG.standard_normal((B, 64, 160)), jnp.float32)
    got = engine.shard_spmm_batched(a, d, mesh=_mesh(4))
    assert got.shape == (B, 64, 160)
    for i in range(B):
        want = spmm_ops.spmm(a[i], d[i], interpret=True)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


def test_shard_spmm_batched_broadcast_dense():
    """(K, N) dense broadcasts across the batch (MoE dispatch shape)."""
    stack = np.stack(
        [random_dense_sparse(RNG, (32, 32), 0.3) for _ in range(4)])
    a = batched_bcsr_from_dense(stack, (8, 8))
    d = jnp.asarray(RNG.standard_normal((32, 128)), jnp.float32)
    got = engine.shard_spmm_batched(a, d, mesh=_mesh(2))
    want = spmm_ops.spmm_batched(a, d, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Bucketed streams (two-phase serving support)
# ---------------------------------------------------------------------------

def test_stream_bucket_law():
    """Power-of-two snap with a floor: the compile-cache-bounding law."""
    assert engine.stream_bucket(1) == 8          # default floor
    assert engine.stream_bucket(8) == 8
    assert engine.stream_bucket(9) == 16
    assert engine.stream_bucket(100) == 128
    assert engine.stream_bucket(128) == 128
    assert engine.stream_bucket(3, minimum=32) == 32
    for n in range(1, 200):
        b = engine.stream_bucket(n)
        assert b >= n and b <= 2 * max(n, 8) and (b & (b - 1)) == 0


def test_with_capacity_pads_zero_blocks_bitwise():
    """nnzb-padded container: same todense, same product, sorted stream,
    row coverage preserved."""
    stack = np.stack(
        [random_dense_sparse(RNG, (32, 64), 0.15) for _ in range(3)])
    a = batched_bcsr_from_dense(stack, (8, 8))
    cap = engine.stream_bucket(a.nnzb)
    ap = a.with_capacity(cap)
    assert ap.nnzb == cap and a.nnzb <= cap
    np.testing.assert_array_equal(np.asarray(ap.todense()),
                                  np.asarray(a.todense()))
    rows = np.asarray(ap.block_rows)
    cols = np.asarray(ap.block_cols)
    assert (np.lexsort((cols, rows)) == np.arange(cap)).all(), "stream sorted"
    with pytest.raises(ValueError, match="can only grow"):
        ap.with_capacity(ap.nnzb - 1)
    assert a.with_capacity(a.nnzb) is a  # no-op fast path


def test_shard_spmm_batched_bucketed_matches_unbucketed():
    """Bucket padding is invisible in the product (zero blocks), and the
    stream length is the bucket."""
    stack = np.stack(
        [random_dense_sparse(RNG, (64, 64), 0.1) for _ in range(4)])
    a = batched_bcsr_from_dense(stack, (8, 8))
    d = jnp.asarray(RNG.standard_normal((4, 64, 160)), jnp.float32)
    got = engine.shard_spmm_batched_bucketed(a, d, mesh=_mesh(4))
    want = engine.shard_spmm_batched(a, d, mesh=_mesh(4))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_shard_spmm_batched_stream_is_trace_safe():
    """The stream entry point runs under jit with the index arrays as
    traced arguments (the phase-2 contract)."""
    stack = np.stack(
        [random_dense_sparse(RNG, (32, 32), 0.3) for _ in range(2)])
    a = spmm_ops.pad_empty_rows(batched_bcsr_from_dense(stack, (8, 8)))
    d = jnp.asarray(RNG.standard_normal((2, 32, 128)), jnp.float32)

    fn = jax.jit(lambda a, d: engine.shard_spmm_batched_stream(
        a, d, mesh=_mesh(2)))
    got = fn(a, d)
    want = engine.shard_spmm_batched(a, d, mesh=_mesh(2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mesh_interning_dedups_equal_meshes():
    """Equal-but-fresh Mesh objects resolve to ONE interned mesh, so the
    lru-cached sharded programs never recompile for a recreated mesh."""
    m1, _ = engine.auto_mesh(_mesh(2))
    m2, _ = engine.auto_mesh(_mesh(2))
    assert m1 is m2
    m3, _ = engine.auto_mesh(make_mesh((2,), ("model",)))
    assert m3 is not m1  # different axis names = different program

    a = bcsr_from_dense(random_dense_sparse(RNG, (32, 32), 0.5), (8, 8))
    b = jnp.asarray(RNG.standard_normal((32, 256)), jnp.float32)
    engine.shard_spmm(a, b, mesh=_mesh(2))
    n_cached = engine._sharded_spmm_fn.cache_info().currsize
    engine.shard_spmm(a, b, mesh=_mesh(2))
    assert engine._sharded_spmm_fn.cache_info().currsize == n_cached


def test_auto_mesh_axes_are_auto_and_results_slice():
    """Every mesh is Auto-axis: a sharded result slices like any array (an
    Explicit-axis mesh raises ShardingTypeError on the slice)."""
    mesh, _ = engine.auto_mesh()
    assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}
    a = bcsr_from_dense(random_dense_sparse(RNG, (32, 32), 0.5), (8, 8))
    b = jnp.asarray(RNG.standard_normal((32, 200)), jnp.float32)
    out = engine.shard_spmm(a, b)
    np.testing.assert_allclose(np.asarray(out[:, :100]),
                               np.asarray(spmm_ref(a, b))[:, :100],
                               rtol=1e-5, atol=1e-5)


def test_constrain_noop_without_mesh_and_loud_with_one():
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import constrain

    x = jnp.ones((8, 8))
    assert constrain(x, P("data", None)) is x        # no mesh set
    with jax.set_mesh(_mesh(2)):
        y = jax.jit(lambda x: constrain(x, P("data", None)))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        with pytest.raises(ValueError, match="nope"):  # no such mesh axis
            jax.jit(lambda x: constrain(x, P("nope", None)))(x)


def test_on_tpu_lets_backend_errors_raise(monkeypatch):
    from repro.kernels import tuning

    def broken():
        raise RuntimeError("backend failed to initialize")
    monkeypatch.setattr(tuning.jax, "default_backend", broken)
    tuning.on_tpu.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="failed to initialize"):
            tuning.on_tpu()
    finally:
        monkeypatch.undo()
        tuning.on_tpu.cache_clear()
    assert tuning.on_tpu() is False


# ---------------------------------------------------------------------------
# SpMSpM: output-column-partitioned
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev", [2, 4])
def test_shard_spmspm_bitwise_matches_single_device(n_dev):
    A = random_dense_sparse(RNG, (24, 96), 0.3)
    B = random_dense_sparse(RNG, (96, 32), 0.1)
    ak, av = spmspm_ops.dense_to_ell_rows(A)
    bk, bv = spmspm_ops.dense_to_ell_cols(B)
    got = engine.shard_spmspm(ak, av, bk, bv, mesh=_mesh(n_dev),
                              rt=8, ct=8)
    want = spmspm_ops.spmspm(ak, av, bk, bv, rt=8, ct=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_shard_spmspm_uneven_columns():
    """C not divisible by n_dev * ct: INVALID-key padding, stripped after."""
    A = random_dense_sparse(RNG, (16, 64), 0.4)
    B = random_dense_sparse(RNG, (64, 22), 0.15)
    ak, av = spmspm_ops.dense_to_ell_rows(A)
    bk, bv = spmspm_ops.dense_to_ell_cols(B)
    got = engine.shard_spmspm(ak, av, bk, bv, mesh=_mesh(4))
    assert got.shape == (16, 22)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(spmspm_ref(ak, av, bk, bv, 64)),
        atol=1e-4, rtol=1e-4)


def test_shard_spmspm_empty_operand():
    """An all-zero B produces an all-zero product (pure INVALID streams)."""
    A = random_dense_sparse(RNG, (16, 64), 0.4)
    B = np.zeros((64, 16), np.float32)
    ak, av = spmspm_ops.dense_to_ell_rows(A)
    bk, bv = spmspm_ops.dense_to_ell_cols(B)
    got = engine.shard_spmspm(ak, av, bk, bv, mesh=_mesh(2))
    np.testing.assert_array_equal(np.asarray(got), np.zeros((16, 16)))
