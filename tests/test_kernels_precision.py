"""Narrow-precision sparse pipeline: the BlockQuant bit-identity contract.

The contract under test (see tests/README.md "Narrow-precision contract"):

* **Kernels are bit-exact vs dequantize-then-f32.**  A quantized spmm /
  spmspm call (narrow fp8/int8 values + f32 scales, f32 resident
  accumulator) must produce *bit-identical* output to dequantizing the
  same container on host and running the wide f32 kernel -- the in-kernel
  dequant is ``values.astype(f32) * scale``, verbatim the host op order,
  followed by the identical dot.  ``assert_array_equal`` everywhere:
  single, batched, ragged-N, bucketed, sharded, any ``nt``.
* **Serving is tolerance-bounded.**  Quantizing the KV cache / expert
  weights changes values by construction; prefill *logits* stay bit-exact
  (quantization touches only the emitted cache), the first decode step is
  error-bounded, and the whole greedy rollout is token-stable for int8 on
  the smoke config.
* **Quantization is strictly opt-in**: scales=None containers and
  kv_quant=None serving paths execute the pre-quantization code
  byte-for-byte.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision
from repro.core.formats import (BCSR, BatchedBCSR, batched_bcsr_from_dense,
                                bcsr_from_dense)
from repro.kernels import engine
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmspm import ops as spmspm_ops
from repro.parallel.mesh import make_mesh

RNG = np.random.default_rng(7)
QUANT = ["fp8_e4m3", "fp8_e5m2", "int8"]


def _block_sparse(rng, shape, density, block=(8, 8)):
    gm, gn = shape[0] // block[0], shape[1] // block[1]
    mask = np.kron(rng.random((gm, gn)) < density, np.ones(block, bool))
    return np.where(mask, rng.standard_normal(shape), 0).astype(np.float32)


# ---------------------------------------------------------------------------
# quantize/dequantize helpers + stochastic rounding determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", QUANT)
def test_quantize_blocks_round_trip_error_bounded(name):
    blocks = jnp.asarray(RNG.standard_normal((6, 8, 8)), jnp.float32)
    vals, scales = precision.quantize_blocks(blocks, name)
    assert vals.dtype == precision.QUANT_DTYPES[name]
    assert scales.shape == (6,) and scales.dtype == jnp.float32
    back = precision.dequantize_blocks(vals, scales)
    # relative error bounded by the format's step size at amax scale
    bound = {"fp8_e4m3": 0.07, "fp8_e5m2": 0.14, "int8": 0.005}[name]
    amax = jnp.abs(blocks).max(axis=(1, 2), keepdims=True)
    assert float(jnp.max(jnp.abs(back - blocks) / amax)) <= bound


def test_quantize_blocks_all_zero_block_gets_unit_scale():
    blocks = jnp.zeros((3, 8, 8), jnp.float32)
    vals, scales = precision.quantize_blocks(blocks, "fp8_e4m3")
    np.testing.assert_array_equal(np.asarray(scales), np.ones(3, np.float32))
    np.testing.assert_array_equal(
        np.asarray(precision.dequantize_blocks(vals, scales)),
        np.zeros((3, 8, 8), np.float32))


@pytest.mark.parametrize("name", QUANT)
def test_stochastic_round_deterministic_across_calls_and_jit(name):
    """Same seed -> bit-identical, eagerly and under jit; different seeds
    differ.  The SR key derives from fold_in(PRNGKey(seed), salt) -- no
    global RNG state anywhere."""
    x = jnp.asarray(RNG.standard_normal((256,)) * 3, jnp.float32)
    a = precision.stochastic_round(x, name, seed=5)
    b = precision.stochastic_round(x, name, seed=5)
    c = jax.jit(lambda v: precision.stochastic_round(v, name, seed=5))(x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    d = precision.stochastic_round(x, name, seed=6)
    assert not (np.asarray(a) == np.asarray(d)).all()


def test_stochastic_round_quantize_blocks_deterministic():
    blocks = jnp.asarray(RNG.standard_normal((4, 8, 8)), jnp.float32)
    v1, s1 = precision.quantize_blocks(blocks, "fp8_e4m3",
                                       rounding="stochastic", seed=11)
    v2, s2 = precision.quantize_blocks(blocks, "fp8_e4m3",
                                       rounding="stochastic", seed=11)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


# ---------------------------------------------------------------------------
# container validation (construction-time dtype/shape consistency)
# ---------------------------------------------------------------------------

def test_bcsr_narrow_blocks_without_scales_rejected():
    a = bcsr_from_dense(_block_sparse(RNG, (64, 64), 0.2), (8, 8))
    with pytest.raises(ValueError, match="scales"):
        BCSR(indptr=a.indptr, block_rows=a.block_rows,
             block_cols=a.block_cols,
             blocks=a.blocks.astype(jnp.float8_e4m3fn),
             shape=a.shape, block=a.block)


def test_bcsr_scale_shape_mismatch_rejected():
    a = bcsr_from_dense(_block_sparse(RNG, (64, 64), 0.2), (8, 8))
    aq = a.quantize("int8")
    with pytest.raises(ValueError) as e:
        BCSR(indptr=aq.indptr, block_rows=aq.block_rows,
             block_cols=aq.block_cols, blocks=aq.blocks,
             shape=aq.shape, block=aq.block,
             scales=aq.scales[:-1])
    assert str(aq.blocks.shape[:1]) in str(e.value)  # shapes in the message


def test_batched_bcsr_scale_consistency_rejected():
    d = np.stack([_block_sparse(RNG, (64, 64), 0.2) for _ in range(3)])
    ab = batched_bcsr_from_dense(d, (8, 8))
    abq = ab.quantize("fp8_e4m3")
    with pytest.raises(ValueError, match="scales"):
        BatchedBCSR(indptr=abq.indptr, block_rows=abq.block_rows,
                    block_cols=abq.block_cols, blocks=abq.blocks,
                    shape=abq.shape, block=abq.block,
                    scales=abq.scales[:, :-1])
    with pytest.raises(ValueError, match="float32"):
        BatchedBCSR(indptr=abq.indptr, block_rows=abq.block_rows,
                    block_cols=abq.block_cols, blocks=abq.blocks,
                    shape=abq.shape, block=abq.block,
                    scales=abq.scales.astype(jnp.float16))


def test_quantize_dequantize_todense_consistent():
    dense = _block_sparse(RNG, (64, 64), 0.2)
    a = bcsr_from_dense(dense, (8, 8))
    aq = a.quantize("int8")
    np.testing.assert_array_equal(np.asarray(aq.todense()),
                                  np.asarray(aq.dequantize().todense()))


# ---------------------------------------------------------------------------
# spmm: bit-exact vs dequantize-then-f32 (the resident-accumulator contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", QUANT)
@pytest.mark.parametrize("nt", [1, 2, 4])
@pytest.mark.parametrize("N", [256, 130])   # aligned and ragged
def test_spmm_quant_bit_identical(name, nt, N):
    a = bcsr_from_dense(_block_sparse(RNG, (64, 64), 0.15), (8, 8))
    aq = a.quantize(name)
    b = jnp.asarray(RNG.standard_normal((64, N)), jnp.float32)
    got = spmm_ops.spmm(aq, b, nt=nt, interpret=True)
    want = spmm_ops.spmm(aq.dequantize(), b, nt=nt, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name", QUANT)
def test_spmm_batched_quant_bit_identical(name):
    d = np.stack([_block_sparse(RNG, (64, 64), 0.15) for _ in range(3)])
    ab = batched_bcsr_from_dense(d, (8, 8)).quantize(name)
    b = jnp.asarray(RNG.standard_normal((3, 64, 128)), jnp.float32)
    got = spmm_ops.spmm_batched(ab, b, interpret=True)
    want = spmm_ops.spmm_batched(ab.dequantize(), b, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_spmm_bucketed_quant_bit_identical():
    """with_capacity pads the scale stream with 1.0 alongside the zero
    blocks; the padded quantized stream must still match exactly."""
    d = np.stack([_block_sparse(RNG, (64, 64), 0.15) for _ in range(2)])
    ab = batched_bcsr_from_dense(d, (8, 8)).quantize("fp8_e4m3")
    abq = ab.with_capacity(ab.nnzb + 16)
    assert abq.scales.shape == (2, ab.nnzb + 16)
    b = jnp.asarray(RNG.standard_normal((2, 64, 128)), jnp.float32)
    got = spmm_ops.spmm_batched(abq, b, interpret=True)
    want = spmm_ops.spmm_batched(abq.dequantize(), b, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a >=2-device mesh")
@pytest.mark.parametrize("name", QUANT)
def test_shard_spmm_quant_bit_identical(name):
    a = bcsr_from_dense(_block_sparse(RNG, (64, 64), 0.15), (8, 8))
    aq = a.quantize(name)
    b = jnp.asarray(RNG.standard_normal((64, 256)), jnp.float32)
    mesh = make_mesh((4,), ("data",))
    got = engine.shard_spmm(aq, b, mesh=mesh)
    want = spmm_ops.spmm(aq.dequantize(), b, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a >=2-device mesh")
def test_shard_spmm_batched_quant_bit_identical():
    d = np.stack([_block_sparse(RNG, (64, 64), 0.15) for _ in range(4)])
    ab = batched_bcsr_from_dense(d, (8, 8)).quantize("int8")
    b = jnp.asarray(RNG.standard_normal((4, 64, 128)), jnp.float32)
    mesh = make_mesh((4,), ("data",))
    got = engine.shard_spmm_batched(ab, b, mesh=mesh)
    want = spmm_ops.spmm_batched(ab.dequantize(), b, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_spmm_wide_path_ignores_quant_machinery():
    """scales=None containers run the pre-quantization path unchanged."""
    a = bcsr_from_dense(_block_sparse(RNG, (64, 64), 0.15), (8, 8))
    assert a.scales is None
    b = jnp.asarray(RNG.standard_normal((64, 128)), jnp.float32)
    out = spmm_ops.spmm(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a.todense() @ b), atol=1e-4)


# ---------------------------------------------------------------------------
# spmspm: narrow A row streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", QUANT)
@pytest.mark.parametrize("nt", [1, 2])
def test_spmspm_quant_bit_identical(name, nt):
    from repro.core.formats import random_dense_sparse

    ad = random_dense_sparse(RNG, (32, 64), 0.2)
    bd = random_dense_sparse(RNG, (64, 32), 0.2)
    ak, av = spmspm_ops.dense_to_ell_rows(ad)
    bk, bv = spmspm_ops.dense_to_ell_cols(bd)
    qv, qs = precision.quantize_rows(jnp.asarray(av), name)
    dq = precision.dequantize_rows(qv, qs)
    got = spmspm_ops.spmspm(ak, qv, bk, bv, nt=nt, interpret=True,
                            a_scales=qs)
    want = spmspm_ops.spmspm(ak, dq, bk, bv, nt=nt, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a >=2-device mesh")
def test_shard_spmspm_quant_bit_identical():
    from repro.core.formats import random_dense_sparse

    ad = random_dense_sparse(RNG, (32, 64), 0.2)
    bd = random_dense_sparse(RNG, (64, 64), 0.2)
    ak, av = spmspm_ops.dense_to_ell_rows(ad)
    bk, bv = spmspm_ops.dense_to_ell_cols(bd)
    qv, qs = precision.quantize_rows(jnp.asarray(av), "fp8_e4m3")
    dq = precision.dequantize_rows(qv, qs)
    mesh = make_mesh((4,), ("data",))
    got = engine.shard_spmspm(ak, qv, bk, bv, mesh=mesh, a_scales=qs)
    want = spmspm_ops.spmspm(ak, dq, bk, bv, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# serving: quantized experts + quantized KV cache (tolerance-bounded)
# ---------------------------------------------------------------------------

TINY = dict(name="tiny-precision", family="moe", d_model=32, n_heads=2,
            n_kv_heads=1, d_ff=48, vocab_size=64,
            block_unit=("attn", "attn+moe"), n_repeats=2, head_dim=16,
            n_experts=4, top_k=1, capacity_factor=1.0,
            moe_shared_expert=True, policy="f32")


@pytest.fixture(scope="module")
def tiny_model():
    from repro.models.config import ArchConfig
    from repro.models import model as M

    cfg = ArchConfig(**TINY)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    return cfg, params, prompts


def test_quantized_experts_bit_identical_vs_dequantized(tiny_model):
    from repro.core.precision import QuantTensor
    from repro.models import moe

    cfg, params, _ = tiny_model
    ffn = jax.tree.map(lambda a: a[0], params["blocks"][1])["ffn"]
    qffn = moe.quantize_expert_weights(ffn, "fp8_e4m3")
    dffn = jax.tree.map(
        lambda w: w.dequantize(jnp.float32) if isinstance(w, QuantTensor)
        else w, qffn, is_leaf=lambda w: isinstance(w, QuantTensor))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 32), jnp.float32)
    out_q, _ = moe.apply_moe(qffn, x, cfg, counts=None, pos=None)
    out_d, _ = moe.apply_moe(dffn, x, cfg, counts=None, pos=None)
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(out_d))


def test_quantize_model_experts_requires_moe(tiny_model):
    from repro.models import moe

    cfg, params, _ = tiny_model
    no_moe = {"blocks": (params["blocks"][0],)}   # the dense-MLP attn slot
    with pytest.raises(ValueError, match="experts"):
        moe.quantize_model_experts(no_moe, "int8")


def test_kv_quant_prefill_logits_bit_exact(tiny_model):
    """kv_quant only changes the *emitted cache*: the prefill forward (and
    its logits) is bit-identical to the wide run."""
    from repro.models import model as M

    cfg, params, prompts = tiny_model
    lg_w, cache_w, _ = M.prefill(params, prompts, cfg, max_seq=14,
                                 cache_dtype=jnp.float32)
    lg_q, cache_q, _ = M.prefill(params, prompts, cfg, max_seq=14,
                                 cache_dtype=jnp.float32,
                                 kv_quant="fp8_e4m3")
    np.testing.assert_array_equal(np.asarray(lg_w), np.asarray(lg_q))
    leaf = cache_q["slots"][0]["attn"]
    assert set(leaf) == {"k", "k_scale", "v", "v_scale"}
    assert leaf["k"].dtype == jnp.float8_e4m3fn
    assert leaf["k_scale"].dtype == jnp.float32


@pytest.mark.parametrize("name", QUANT)
def test_kv_quant_first_decode_step_error_bounded(tiny_model, name):
    from repro.models import model as M

    cfg, params, prompts = tiny_model

    def first_step(kv_quant):
        lg, cache, pos = M.prefill(params, prompts, cfg, max_seq=14,
                                   cache_dtype=jnp.float32,
                                   kv_quant=kv_quant)
        tok = jnp.argmax(lg[:, -1:], axis=-1).astype(jnp.int32)
        out, _ = M.decode_step(params, cfg, cache, pos, tok)
        return np.asarray(out)

    ref = first_step(None)
    got = first_step(name)
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.2, f"{name}: first-decode relative error {rel:.3f}"


@pytest.mark.serve
def test_kv_quant_int8_greedy_tokens_stable(tiny_model):
    """int8 KV + int8 experts reproduce the f32 loop's greedy tokens on the
    smoke config (the tightest quantizer; fp8 is tolerance-only)."""
    from repro.launch.serve import ServeLoop

    cfg, params, prompts = tiny_model
    base = ServeLoop(params, cfg, max_seq=14).run(prompts, 6)
    quant = ServeLoop(params, cfg, max_seq=14, quantize_experts="int8",
                      kv_quant="int8").run(prompts, 6)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(quant))


@pytest.mark.serve
def test_kv_quant_scheduler_matches_static_loop(tiny_model):
    """Continuous batching with a quantized cache pool: per-request tokens
    match the quantized static loop (per-row scatter of narrow values AND
    scales)."""
    from repro.launch.serve import ServeLoop, ServeScheduler

    cfg, params, prompts = tiny_model
    sched = ServeScheduler(params, cfg, max_seq=14, max_slots=2,
                           quantize_experts="int8", kv_quant="int8")
    r1 = sched.submit(np.asarray(prompts[0]), 6)
    r2 = sched.submit(np.asarray(prompts[1]), 6)
    out = sched.run()
    seq = ServeLoop(params, cfg, max_seq=14, quantize_experts="int8",
                    kv_quant="int8").run(prompts, 6)
    np.testing.assert_array_equal(np.asarray(out[r1.uid]), np.asarray(seq[0]))
    np.testing.assert_array_equal(np.asarray(out[r2.uid]), np.asarray(seq[1]))


# ---------------------------------------------------------------------------
# checkpoint: lossless quantized round-trip
# ---------------------------------------------------------------------------

def test_checkpoint_quantized_round_trip(tmp_path):
    """np.savez degrades ml_dtypes (bf16/fp8) leaves to void records; the
    manager byte-packs them, so narrow params restore bit-exact with their
    true dtypes (QuantTensor leaves ride the pytree)."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.precision import QuantTensor, quantize_tensor

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16))
    state = {"wide": x.astype(jnp.float32),
             "bf16": x.astype(jnp.bfloat16),
             "qt": quantize_tensor(x, "fp8_e4m3", axis=-2),
             "int8q": quantize_tensor(x, "int8", axis=-1)}
    mgr = CheckpointManager(tmp_path)
    mgr.save(0, state)
    like = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), state)
    restored, step = mgr.restore(like)
    assert step == 0
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    q = restored["qt"]
    assert isinstance(q, QuantTensor) and q.axis == -2
    assert q.values.dtype == jnp.float8_e4m3fn
