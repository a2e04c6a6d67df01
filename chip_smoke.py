#!/usr/bin/env python3
"""Bring-up smoke run of the system on one TPU chip.

    python chip_smoke.py            # one chip: every phase below
    python chip_smoke.py --chips 4  # four chips: the sharded engine only

Everything runs in this one process (a chip belongs to one process), in
phases; the first failure exits non-zero.

1. device  -- require a TPU (there is no CPU fallback); print its kind.
2. kernels -- the Pallas kernels at real sizes, compiled with
   ``interpret=False`` and the TPU tile rows, each checked against its
   reference and for a ``tpu_custom_call`` in its compiled HLO.
3. dense   -- qwen3-1.7b (28 layers, published widths, vocab 151936) served
   by ``ServeScheduler`` and cross-checked against ``ServeLoop``.
4. moe     -- llama4-scout at published widths cut to one layer, served by
   ``ServeScheduler`` with bcsr dispatch (the SpMM kernel inside the fused
   decode step) and with gather dispatch, which must agree token for
   token.

``--chips 4`` runs only the sharded engine (``engine.shard_*``) on a
4-device mesh, bit for bit against the same kernel on one device.

Weights and data are random, made from ``--seed``.  The times and memory
figures printed are bring-up figures, not benchmark results.  The last line
of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Kernel sizes (the compile tests in tests/test_tpu_compile.py use these).
SPMM_M = SPMM_K = 4096          # A: seeded BCSR, 8x8 blocks
SPMM_N = 2048                   # dense operand columns
SPMM_BLOCK = (8, 8)
SPMM_DENSITY = 0.05             # block density of A
SPMSPM_N = 2048                 # A, B: (SPMSPM_N, SPMSPM_N) padded ELL
SPMSPM_DENSITY = 0.01
STENCIL = "j3d27pt"
STENCIL_N = 256                 # interior points per dim
FLASH_SHAPE = dict(B=1, Hq=16, Hkv=8, S=4096, hd=128)   # bf16
FLASH_WINDOW = 1024             # sliding window of the masked/sparse forms
# Tiles of the --chips 4 run, fixed so the sharded and one-device programs
# walk the same tiles (the bit-for-bit contract needs that).
SHARD_SPMM_TILES = dict(bn=256, nt=2)
SHARD_SPMSPM_TILES = dict(rt=16, ct=128, nt=1)

# Normalised max error, max|out - ref| / max|ref|, allowed per kernel.  The
# SpMM, SpMSpM and stencil kernels compute at f32 accuracy (an f32 matmul as
# one bf16 MXU pass reads about 3e-3 and fails); flash attention's
# probabilities and outputs are bf16 (one bf16 rounding is 2**-8 = 3.9e-3).
TOL = {"spmm_f32": 1e-5, "spmm_bf16": 1e-5, "spmm_int8": 1e-5,
       "spmspm": 1e-6, "stencil": 1e-6, "flash": 1e-2}

# Serving: prompts of a few lengths (few prefill shapes), greedy decode.
SERVE = dict(requests=8, prompt_lens=(128, 256, 512), gen=32, slots=4,
             max_seq=1024)


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------- operands --

def _block_stream(rng, m: int, k: int):
    """Sorted BCSR index stream of a seeded (m, k) block mask at
    SPMM_DENSITY: (indptr, block_rows, block_cols)."""
    bm, bk = SPMM_BLOCK
    gm = m // bm
    brows, bcols = np.nonzero(rng.random((gm, k // bk)) < SPMM_DENSITY)
    indptr = np.zeros(gm + 1, np.int32)
    np.cumsum(np.bincount(brows, minlength=gm), out=indptr[1:])
    return indptr, brows.astype(np.int32), bcols.astype(np.int32)


def spmm_operands(seed: int):
    """(A, dense): seeded (SPMM_M, SPMM_K) BCSR times (SPMM_K, SPMM_N), f32."""
    import jax.numpy as jnp
    from repro.core.formats import BCSR

    rng = np.random.default_rng(seed)
    indptr, rows, cols = _block_stream(rng, SPMM_M, SPMM_K)
    blocks = rng.standard_normal((len(rows),) + SPMM_BLOCK, np.float32)
    a = BCSR(indptr=jnp.asarray(indptr), block_rows=jnp.asarray(rows),
             block_cols=jnp.asarray(cols), blocks=jnp.asarray(blocks),
             shape=(SPMM_M, SPMM_K), block=SPMM_BLOCK)
    dense = rng.standard_normal((SPMM_K, SPMM_N), np.float32)
    return a, jnp.asarray(dense)


def batched_spmm_operands(seed: int, batch: int = 8):
    """(A, dense): ``batch`` quarter-size BCSR matrices on one shared index
    stream (the MoE shape) times per-matrix dense operands, f32."""
    import jax.numpy as jnp
    from repro.core.formats import BatchedBCSR

    rng = np.random.default_rng(seed)
    m, k, n = SPMM_M // 4, SPMM_K // 4, SPMM_N // 4
    indptr, rows, cols = _block_stream(rng, m, k)
    blocks = rng.standard_normal((batch, len(rows)) + SPMM_BLOCK, np.float32)
    a = BatchedBCSR(indptr=jnp.asarray(indptr), block_rows=jnp.asarray(rows),
                    block_cols=jnp.asarray(cols), blocks=jnp.asarray(blocks),
                    shape=(batch, m, k), block=SPMM_BLOCK)
    dense = rng.standard_normal((batch, k, n), np.float32)
    return a, jnp.asarray(dense)


def spmspm_operands(seed: int):
    """(a_keys, a_vals, b_keys, b_vals): padded-ELL rows of A and columns
    of B, both (SPMSPM_N, SPMSPM_N) at SPMSPM_DENSITY."""
    from repro.core.formats import random_dense_sparse
    from repro.kernels.spmspm import ops as spmspm_ops

    rng = np.random.default_rng(seed)
    shape = (SPMSPM_N, SPMSPM_N)
    ak, av = spmspm_ops.dense_to_ell_rows(
        random_dense_sparse(rng, shape, SPMSPM_DENSITY))
    bk, bv = spmspm_ops.dense_to_ell_cols(
        random_dense_sparse(rng, shape, SPMSPM_DENSITY))
    return ak, av, bk, bv


def flash_operands(seed: int):
    import jax
    import jax.numpy as jnp

    s = FLASH_SHAPE
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (s["B"], s["Hq"], s["S"], s["hd"]), jnp.bfloat16)
    k = jax.random.normal(kk, (s["B"], s["Hkv"], s["S"], s["hd"]), jnp.bfloat16)
    v = jax.random.normal(kv, (s["B"], s["Hkv"], s["S"], s["hd"]), jnp.bfloat16)
    return q, k, v


def window_mask():
    """The sliding-window BlockMask at the TPU block-sparse tile row."""
    import jax.numpy as jnp
    from repro.core.masks import BlockMask
    from repro.kernels import tuning

    s = FLASH_SHAPE
    bq, bk = tuning.flash_sparse_tiles(s["S"], s["S"], s["hd"], jnp.bfloat16,
                                       pattern="window")
    return BlockMask.sliding_window(s["S"], s["S"], FLASH_WINDOW, bq=bq,
                                    bk=bk)


# ----------------------------------------------------------------- timing --

class PhaseClock:
    """Wall time of a phase and the part of it spent lowering and compiling
    (JAX's own compile events, persistent-cache reads included)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.compile_s += duration

    def run(self, name: str, fn, device):
        c0, t0 = self.compile_s, time.monotonic()
        out = fn()
        wall = time.monotonic() - t0
        comp = self.compile_s - c0
        stats = device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        peak_s = "not reported" if peak is None else f"{peak} B"
        print(f"[bring-up figure, not a benchmark result] phase {name}: "
              f"wall {wall:.2f} s, compile {comp:.2f} s "
              f"({100 * comp / max(wall, 1e-9):.0f}% of wall), "
              f"peak device memory {peak_s}", flush=True)
        return out


# ---------------------------------------------------------------- phases --

def require_tpu(n_chips: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU: JAX could not start a backend ({e})")
    d = devices[0]
    check(d.platform == "tpu",
          f"no TPU: JAX found platform {d.platform!r}; this smoke run has "
          f"no CPU fallback")
    check(len(devices) >= n_chips,
          f"needs {n_chips} TPU chips, JAX found {len(devices)}")
    print(f"device: {d.device_kind}, {len(devices)} chip(s)", flush=True)
    return devices


def compiled_kernel(fn, *args):
    """jit ``fn``, compile it for ``args`` and require a Mosaic kernel in
    the compiled HLO (an interpreted kernel lowers to plain XLA ops)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
          f"compiled HLO -- the kernel did not lower to Mosaic")
    return compiled


def max_error(name: str, out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    check(out.shape == ref.shape, f"{name}: shape {out.shape} != {ref.shape}")
    check(np.isfinite(out).all(), f"{name}: non-finite output")
    err = float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))
    tol = TOL[name.split(":")[0]]
    print(f"kernel {name}: max error {err:.3e} (normalised, tolerance "
          f"{tol:.0e})", flush=True)
    check(err <= tol, f"{name}: max error {err:.3e} > tolerance {tol:.0e}")
    return err


def run_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.stencils import STENCILS
    from repro.kernels.flash_attention import ops as fops
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.spmm import ops as spmm_ops
    from repro.kernels.spmm.ref import spmm_ref
    from repro.kernels.spmspm import ops as spmspm_ops
    from repro.kernels.spmspm.ref import spmspm_ref
    from repro.kernels.stencil import ops as stencil_ops
    from repro.kernels.stencil.ref import stencil_ref

    exact = jax.default_matmul_precision("highest")

    # SpMM: f32, bf16 and int8 per-block-scaled values.  A's stream is
    # closed over (host-side row padding needs concrete indices).
    a, b = spmm_operands(seed)
    a_bf16 = dataclasses.replace(a, blocks=a.blocks.astype(jnp.bfloat16))
    a_int8 = a.quantize("int8")
    cases = [
        ("spmm_f32", a, b, a),
        ("spmm_bf16", a_bf16, b.astype(jnp.bfloat16),
         dataclasses.replace(a_bf16, blocks=a_bf16.blocks.astype(jnp.float32))),
        ("spmm_int8", a_int8, b, a_int8.dequantize()),
    ]
    for name, a_k, b_k, a_ref in cases:
        def spmm(dense, a_k=a_k):
            return spmm_ops.spmm(a_k, dense, interpret=False)
        out = compiled_kernel(spmm, b_k)(b_k)
        with exact:
            ref = spmm_ref(a_ref, b_k.astype(jnp.float32))
        max_error(name, out, ref)
    del a, b, a_bf16, a_int8, cases

    # SpMSpM on padded-ELL streams.
    ak, av, bk, bv = spmspm_operands(seed)

    def spmspm(ak, av, bk, bv):
        return spmspm_ops.spmspm(ak, av, bk, bv, interpret=False)
    args = [jnp.asarray(x) for x in (ak, av, bk, bv)]
    out = compiled_kernel(spmspm, *args)(*args)
    max_error("spmspm", out, spmspm_ref(ak, av, bk, bv, SPMSPM_N))

    # The paper's headline stencil: j3d27pt on a 256^3 f32 grid.
    spec = STENCILS[STENCIL]
    g = STENCIL_N + 2 * spec.radius
    grid = jax.random.normal(jax.random.PRNGKey(seed), (g, g, g), jnp.float32)

    def stencil(grid):
        return stencil_ops.apply(grid, spec, interpret=False)
    out = compiled_kernel(stencil, grid)(grid)
    max_error(f"stencil:{STENCIL}", out, stencil_ref(grid, spec))
    del grid, out

    # Flash attention: dense causal, masked-dense and sparse sliding window.
    q, k, v = flash_operands(seed)
    mask = window_mask()
    forms = [
        ("flash:dense_causal", dict(causal=True), dict(causal=True)),
        ("flash:masked_window", dict(mask=mask, mask_impl="dense"),
         dict(mask=mask)),
        ("flash:sparse_window", dict(mask=mask, mask_impl="sparse"),
         dict(mask=mask)),
    ]
    for name, kw, ref_kw in forms:
        def attn(q, k, v, kw=kw):
            return fops.attention(q, k, v, interpret=False, **kw)
        out = compiled_kernel(attn, q, k, v)(q, k, v)
        with exact:
            ref = attention_ref(q, k, v, **ref_kw)
        max_error(name, out, ref)


def _serve(params, cfg, prompts, **kw):
    from repro.launch.serve import ServeScheduler

    sched = ServeScheduler(params, cfg, max_seq=SERVE["max_seq"],
                           max_slots=SERVE["slots"], **kw)
    for p in prompts:
        sched.submit(p, SERVE["gen"])
    out = sched.run()
    s = sched.summary()
    req = s["requests"]
    check(req["finished"] == len(prompts) and len(out) == len(prompts),
          f"{cfg.name}: {req['finished']}/{len(prompts)} requests finished "
          f"({req})")
    counters = s["health"]["counters"]
    check(req["failed"] == req["shed"] == req["retries"] == 0
          and not counters,
          f"{cfg.name}: unhealthy run: requests {req}, health {counters}")
    fallbacks = s["timing"]["attention_ref_fallbacks"]
    check(fallbacks == 0,
          f"{cfg.name}: {fallbacks} attention reference fallback(s)")
    for uid, toks in out.items():
        check(len(toks) == SERVE["gen"]
              and ((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"{cfg.name}: request {uid} tokens out of range or short")
    print(f"serve {cfg.name} {kw}: {len(out)} requests x {SERVE['gen']} "
          f"tokens, all finished, no failed/shed/retried request, 0 "
          f"attention fallbacks, every token in vocab", flush=True)
    return out


def _prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.choice(SERVE["prompt_lens"], size=SERVE["requests"])
    return [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
            for n in lens]


def _init_params(cfg, seed: int):
    import jax
    from repro.models import model as M

    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    return jax.block_until_ready(params)


def run_dense(seed: int) -> None:
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.serve import ServeLoop

    cfg = get_config("qwen3-1.7b")
    print(f"dense config: {cfg.name}, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size} (uncut)", flush=True)
    params = _init_params(cfg, seed)
    prompts = _prompts(cfg, seed)
    out = _serve(params, cfg, prompts)
    loop = ServeLoop(params, cfg, max_seq=SERVE["max_seq"])
    ref = loop.run(jnp.asarray(prompts[0])[None], SERVE["gen"])[0]
    check(np.array_equal(ref, out[0]),
          f"{cfg.name}: scheduler request 0 {out[0][:8].tolist()}... != "
          f"ServeLoop {ref[:8].tolist()}...")
    print(f"serve {cfg.name}: request 0 token-identical to ServeLoop",
          flush=True)


def run_moe(seed: int) -> None:
    from repro.configs import get_config

    base = get_config("llama4-scout-17b-a16e")
    cfg = dataclasses.replace(base, n_repeats=1,
                              vocab_size=base.vocab_size // 8)
    print(f"moe config: {cfg.name} at published widths (d_model "
          f"{cfg.d_model}, {cfg.n_experts} experts, d_ff {cfg.d_ff}); cut: "
          f"layers {base.n_layers} -> {cfg.n_layers} (one period of "
          f"{base.block_unit}), vocab {base.vocab_size} -> {cfg.vocab_size}",
          flush=True)
    params = _init_params(cfg, seed)
    prompts = _prompts(cfg, seed)
    runs = {d: _serve(params, cfg, prompts, dispatch=d)
            for d in ("bcsr", "gather")}
    for uid, toks in runs["bcsr"].items():
        check(np.array_equal(toks, runs["gather"][uid]),
              f"{cfg.name}: request {uid} bcsr {toks[:8].tolist()}... != "
              f"gather {runs['gather'][uid][:8].tolist()}...")
    print(f"serve {cfg.name}: bcsr tokens == gather tokens for all "
          f"{len(prompts)} requests", flush=True)


def run_sharded(seed: int, devices) -> None:
    """The sharded engine on a 4-chip Auto-axis mesh, bit for bit against
    the same kernel on one device."""
    from repro.kernels import engine
    from repro.kernels.flash_attention import ops as fops
    from repro.kernels.spmm import ops as spmm_ops
    from repro.kernels.spmspm import ops as spmspm_ops
    from repro.parallel.mesh import make_mesh

    n = 4
    mesh = make_mesh((n,), ("data",), devices=devices[:n])

    def same(name, got, want):
        spans = len(got.sharding.device_set)
        check(spans == n, f"{name}: output spans {spans} device(s), not {n}")
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              f"{name}: sharded result differs from the one-device kernel")
        print(f"sharded {name}: bit-for-bit equal to one device, output on "
              f"{spans} devices", flush=True)

    a, b = spmm_operands(seed)
    tiles = SHARD_SPMM_TILES
    same("shard_spmm", engine.shard_spmm(a, b, mesh=mesh, **tiles),
         spmm_ops.spmm(a, b, interpret=False, **tiles))

    ab, db = batched_spmm_operands(seed)
    same("shard_spmm_batched",
         engine.shard_spmm_batched(ab, db, mesh=mesh, **tiles),
         spmm_ops.spmm_batched(ab, db, interpret=False, **tiles))

    ak, av, bk, bv = spmspm_operands(seed)
    st = SHARD_SPMSPM_TILES
    same("shard_spmspm", engine.shard_spmspm(ak, av, bk, bv, mesh=mesh, **st),
         spmspm_ops.spmspm(ak, av, bk, bv, interpret=False, **st))

    q, k, v = flash_operands(seed)
    mask = window_mask()
    same("shard_attention_sparse",
         engine.shard_attention_sparse(q, k, v, mask, mesh=mesh),
         fops.attention(q, k, v, mask=mask, mask_impl="sparse",
                        interpret=False))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the sharded engine on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    try:
        devices = require_tpu(args.chips)
        clock = PhaseClock()
        if args.chips == 4:
            clock.run("sharded", lambda: run_sharded(args.seed, devices),
                      devices[0])
        else:
            clock.run("kernels", lambda: run_kernels(args.seed), devices[0])
            clock.run("dense", lambda: run_dense(args.seed), devices[0])
            clock.run("moe", lambda: run_moe(args.seed), devices[0])
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
