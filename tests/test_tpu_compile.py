"""Compile the main-path Pallas kernels for a TPU v5e chip that is described,
not attached, at the sizes ``chip_smoke.py`` runs them -- on one chip, and
the sharded engine on a 4-chip (v5e:2x2) mesh.

Interpret mode cannot see what Mosaic refuses (misaligned blocks, vector ops
it cannot lower, VMEM overflow); this compile can, with no chip.  Each test
compiles with ``interpret=False`` and the TPU tile rows of
``repro.kernels.tuning``, and requires a ``tpu_custom_call`` in the compiled
HLO.  Nothing runs, so nothing here says anything about results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  All such compiles stay in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke as smoke
from repro.configs import get_config
from repro.core.stencils import STENCILS
from repro.kernels import engine, tuning
from repro.kernels.flash_attention import ops as fops
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmspm import ops as spmspm_ops
from repro.kernels.stencil import ops as stencil_ops
from repro.parallel.mesh import make_mesh
from repro.models import moe


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_rows(monkeypatch):
    """Pick the TPU rows of the tile table, as on the chip."""
    monkeypatch.setattr(tuning, "on_tpu", lambda: True)


def _compile(fn, *args, **static):
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"


def _sds(one_chip, x):
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=one_chip)


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
def test_spmm_bcsr(one_chip, tpu_rows, dtype):
    a, b = smoke.spmm_operands(0)
    blocks = {"f32": jnp.float32, "bf16": jnp.bfloat16,
              "int8": jnp.int8}[dtype]
    dense = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    nnzb = a.nnzb
    tiles = tuning.spmm_tiles(smoke.SPMM_N, blocks if dtype == "int8"
                              else dense)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(spmm_ops._spmm_jit, S((nnzb,), jnp.int32), S((nnzb,), jnp.int32),
             S((nnzb,) + smoke.SPMM_BLOCK, blocks),
             S((smoke.SPMM_K, smoke.SPMM_N), dense),
             S((nnzb,), jnp.float32) if dtype == "int8" else None,
             n_block_rows=smoke.SPMM_M // smoke.SPMM_BLOCK[0],
             out_dtype=jnp.float32, interpret=False, **tiles)


def test_spmspm(one_chip, tpu_rows):
    ak, av, bk, bv = smoke.spmspm_operands(0)
    R, C = ak.shape[0], bk.shape[0]
    rt, ct = tuning.spmspm_tiles(R, C, ak.shape[1], bk.shape[1], av.dtype)
    nt = tuning.spmspm_nt(C, ct, bk.shape[1], av.dtype)
    assert R % rt == 0 and C % (nt * ct) == 0
    _compile(spmspm_ops._spmspm_jit, *(_sds(one_chip, x)
                                       for x in (ak, av, bk, bv)),
             None, rt=rt, ct=ct, nt=nt, interpret=False)


def test_moe_dispatch_spmm(topo, tpu_rows):
    """The bcsr MoE dispatch as served: the engine's shard_map-wrapped
    batched SpMM on a one-chip mesh at llama4-scout's d_model (5120), a
    512-token prefill routed over its 16 experts."""
    cfg = get_config("llama4-scout-17b-a16e")
    tokens = max(smoke.SERVE["prompt_lens"])
    cap = moe.dispatch_capacity(tokens, cfg)
    tiles = tuning.moe_dispatch_tiles(cfg.d_model, jnp.bfloat16)
    bm, bk = tiles["block"]
    _, _, sp, gm, _ = moe._dispatch_grid(tokens, cfg.n_experts, cap, bm, bk)
    nnzb = engine.stream_bucket(tokens, minimum=tiles["min_bucket"])
    width = tiles["nt"] * tiles["bn"]
    n_pad = -(-cfg.d_model // width) * width
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    fn = engine._sharded_spmm_batched_fn(mesh, "data", gm, tiles["bn"],
                                         tiles["nt"], "bfloat16", False)
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    _compile(fn, S((nnzb,), jnp.int32), S((nnzb,), jnp.int32),
             S((1, nnzb, bm, bk), jnp.bfloat16),
             S((1, sp, n_pad), jnp.bfloat16))


def test_gdn_decode(one_chip, tpu_rows):
    """The Gated DeltaNet decode kernel at qwen3-next-80b-a3b.longgen's
    shapes: 64 rows, 32 value heads, a (128, 128) float32 state each, with
    the state aliased in place."""
    from repro.kernels.gdn import ops as gdn_ops
    cfg = get_config("qwen3-next-80b-a3b")
    B, H, D = 64, cfg.gdn_v_heads, cfg.gdn_v_head_dim
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    _compile(jax.jit(gdn_ops.gdn_decode), S(B, H, D), S(B, H, D),
             S(B, H, D), S(B, H), S(B, H), S(B, H, D, D))


def _flash_args(one_chip):
    s = smoke.FLASH_SHAPE
    S = lambda h: jax.ShapeDtypeStruct((s["B"], h, s["S"], s["hd"]),
                                       jnp.bfloat16, sharding=one_chip)
    return S(s["Hq"]), S(s["Hkv"]), S(s["Hkv"])


def test_flash_dense(one_chip, tpu_rows):
    s = smoke.FLASH_SHAPE
    bq, bk = tuning.flash_tiles(s["S"], s["S"], s["hd"], jnp.bfloat16)
    _compile(fops._attention_jit, *_flash_args(one_chip), causal=True,
             window=None, bq=bq, bk=bk, interpret=False)


def test_flash_masked(one_chip, tpu_rows):
    s, mask = smoke.FLASH_SHAPE, smoke.window_mask()
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    _compile(fops._masked_jit, *_flash_args(one_chip),
             S(mask.tile_kinds.shape), S((1,)), window=mask.window,
             skv=s["S"], bq=mask.bq, bk=mask.bk, sq=s["S"], interpret=False)


def test_flash_sparse(one_chip, tpu_rows):
    s, mask = smoke.FLASH_SHAPE, smoke.window_mask()
    cap = mask.lower(bucket=True).capacity
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    _compile(fops._sparse_jit, *_flash_args(one_chip), S((cap,)), S((cap,)),
             S((cap,)), S((1,)), window=mask.window, skv=s["S"], bq=mask.bq,
             bk=mask.bk, sq=s["S"], interpret=False)


@pytest.mark.parametrize("name,interior", [
    ("j2d9pt", (4096, 4096)),
    (smoke.STENCIL, (smoke.STENCIL_N,) * 3),
])
def test_stencil(one_chip, tpu_rows, name, interior):
    spec = STENCILS[name]
    shape = tuple(n + 2 * spec.radius for n in interior)
    _compile(stencil_ops.apply,
             jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip),
             spec, interpret=False)


def _sharded_case(name, mesh):
    """(compiled-program builder, argument shapes) of one sharded-engine
    entry at the sizes and tiles ``chip_smoke.py --chips 4`` runs it."""
    S = lambda shape, dt, *spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P(*spec)))
    i32, f32 = jnp.int32, jnp.float32
    bn, nt = smoke.SHARD_SPMM_TILES["bn"], smoke.SHARD_SPMM_TILES["nt"]
    if name == "shard_spmm":
        a = spmm_ops.pad_empty_rows(smoke.spmm_operands(0)[0])
        fn = engine._sharded_spmm_fn(mesh, "data", a.grid_shape[0], bn, nt,
                                     "float32", False)
        return fn, (S((a.nnzb,), i32), S((a.nnzb,), i32),
                    S((a.nnzb,) + smoke.SPMM_BLOCK, f32),
                    S((smoke.SPMM_K, smoke.SPMM_N), f32, None, "data"))
    if name == "shard_spmm_batched":
        a, d = smoke.batched_spmm_operands(0)
        a = spmm_ops.pad_empty_rows(a)
        fn = engine._sharded_spmm_batched_fn(mesh, "data", a.grid_shape[0],
                                             bn, nt, "float32", False)
        return fn, (S((a.nnzb,), i32), S((a.nnzb,), i32),
                    S(a.blocks.shape, f32, "data"), S(d.shape, f32, "data"))
    if name == "shard_spmspm":
        ak, av, bk, bv = smoke.spmspm_operands(0)
        t = smoke.SHARD_SPMSPM_TILES
        fn = engine._sharded_spmspm_fn(mesh, "data", t["rt"], t["ct"],
                                       t["nt"], "float32", False)
        return fn, (S(ak.shape, i32), S(av.shape, f32),
                    S(bk.shape, i32, "data"), S(bv.shape, f32, "data"))
    s, mask = smoke.FLASH_SHAPE, smoke.window_mask()
    n = mesh.size
    cap = engine.stream_bucket(max(m.lower(bucket=False).capacity
                                   for m in mask.shard_rows(n)))
    fn = engine._sharded_attention_sparse_fn(
        mesh, "data", s["S"] // n, s["S"], mask.window, mask.bq, mask.bk,
        None, False)
    kv = S((s["B"], s["Hkv"], s["S"], s["hd"]), jnp.bfloat16)
    return fn, (S((s["B"], s["Hq"], s["S"], s["hd"]), jnp.bfloat16, None,
                  None, "data"), kv, kv,
                *[S((n, cap), i32, "data")] * 3)


@pytest.mark.parametrize("name", ["shard_spmm", "shard_spmm_batched",
                                  "shard_spmspm", "shard_attention_sparse"])
def test_sharded_engine_four_chips(topo, name):
    """The ``--chips 4`` path: each engine entry on a 4-chip Auto-axis mesh
    must partition around its kernel (one Mosaic call per device)."""
    mesh = make_mesh((4,), ("data",), devices=topo.devices[:4])
    fn, args = _sharded_case(name, mesh)
    _compile(fn, *args)
