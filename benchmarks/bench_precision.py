"""Paper Tab. 1 / multi-precision ladder + the narrow-precision sparse sweep.

Occamy's FP64/32/16/8 SIMD ladder maps to the v5e MXU's f32/bf16/fp8 modes
(DESIGN.md S2.1): each narrowing step doubles peak FLOP/s; accumulation
always widens to f32 (the ExSdotp pattern). CPU wall times are emulation
artifacts for narrow types; the TPU-projected peaks are the Tab. 1 row.

Beyond the ladder, this bench now *measures* the per-block-scaled narrow
pipeline end to end (``BENCH_precision.json``):

* **spmm kernel sweep** -- the BCSR x dense kernel at f32 vs quantized
  fp8_e4m3 / fp8_e5m2 / int8 block values (per-block f32 scales, f32
  resident accumulator): wall time, effective GFLOP/s, max-abs error vs
  the f32 kernel, and the bit-identity check vs the
  dequantize-on-host-then-f32-kernel reference (the BlockQuant contract).
* **serving sweep** -- a tiny attn+moe arch through ``launch.serve
  .ServeLoop`` per narrow dtype with quantized expert weights AND a
  quantized KV cache: decode tok/s, greedy-token agreement with the f32
  loop, and the first-decode-step logit error (the tolerance-bounded
  serving contract; see tests/README.md "Narrow-precision contract").

Run modes:
  python benchmarks/bench_precision.py           # full sweep -> BENCH json
  python benchmarks/bench_precision.py --smoke   # CI-sized, same schema
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import PEAK_FLOPS, emit_bench, row, time_fn
from repro.core.precision import LADDER, PEAK_MULTIPLIER, policy

M = N = K = 1024

QUANT_NAMES = ("fp8_e4m3", "fp8_e5m2", "int8")


def _ladder_rows() -> list:
    rng = np.random.default_rng(0)
    rows = []
    a32 = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    b32 = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    flops = 2 * M * N * K
    for name in LADDER:
        pol = policy(name)

        @jax.jit
        def mm(a, b, pol=pol):
            return pol.dot(a, b)

        t = time_fn(mm, a32, b32)
        out = mm(a32, b32)
        assert out.dtype == jnp.float32, "accumulation must widen to f32"
        tpu_peak = PEAK_FLOPS["f32"] * PEAK_MULTIPLIER[name]
        rows.append(row(
            f"precision/{name}/widening_matmul", t * 1e6,
            f"cpu_gflops={flops / t / 1e9:.2f};"
            f"tpu_peak_tflops={tpu_peak / 1e12:.0f};"
            f"tpu_time_at_peak_us={flops / tpu_peak * 1e6:.2f};"
            f"accum=f32"))
    return rows


def _spmm_sweep(*, smoke: bool) -> dict:
    """Quantized-BCSR spmm vs the f32 kernel on one block-uniform case."""
    from repro.core.formats import bcsr_from_dense
    from repro.kernels.spmm import ops as spmm_ops

    m = k = 128 if smoke else 512
    n = 128 if smoke else 256
    density = 0.1
    rng = np.random.default_rng(0)
    gm, gk = m // 8, k // 8
    mask = np.kron(rng.random((gm, gk)) < density, np.ones((8, 8), bool))
    a_dense = np.where(mask, rng.standard_normal((m, k)), 0).astype(np.float32)
    a = bcsr_from_dense(a_dense, (8, 8))
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    useful = spmm_ops.flops(a, n)

    t_ref = time_fn(lambda b_: spmm_ops.spmm(a, b_, interpret=True), b)
    out_ref = np.asarray(spmm_ops.spmm(a, b, interpret=True))
    scale = float(np.abs(out_ref).max()) or 1.0
    points = {"f32": {"time_us": t_ref * 1e6,
                      "gflops": useful / t_ref / 1e9,
                      "max_abs_err": 0.0, "rel_err": 0.0,
                      "nnzb": int(a.nnzb)}}
    for name in QUANT_NAMES:
        aq = a.quantize(name)
        t = time_fn(lambda b_: spmm_ops.spmm(aq, b_, interpret=True), b)
        out_q = np.asarray(spmm_ops.spmm(aq, b, interpret=True))
        # BlockQuant bit-identity contract: the in-kernel dequant must match
        # dequantizing on host and running the wide kernel exactly
        out_dq = np.asarray(spmm_ops.spmm(aq.dequantize(), b, interpret=True))
        err = float(np.abs(out_q - out_ref).max())
        points[name] = {
            "time_us": t * 1e6,
            "gflops": useful / t / 1e9,
            "max_abs_err": err,
            "rel_err": err / scale,
            "bit_identical_vs_dequant_ref": bool((out_q == out_dq).all()),
            "nnzb": int(aq.nnzb),
        }
    return {"case": {"m": m, "k": k, "n": n, "block": [8, 8],
                     "density": density},
            "points": points}


def _serving_sweep(*, smoke: bool) -> dict:
    """Quantized experts + quantized KV through ServeLoop vs the f32 loop."""
    from repro.models import model as M_
    from repro.models.config import ArchConfig
    from repro.launch.serve import ServeLoop

    cfg = ArchConfig(
        name="tiny-precision-bench", family="moe", d_model=32, n_heads=2,
        n_kv_heads=1, d_ff=48, vocab_size=64,
        block_unit=("attn", "attn+moe"), n_repeats=2, head_dim=16,
        n_experts=4, top_k=1, capacity_factor=1.0, moe_shared_expert=True,
        policy="f32")
    B, P, G = (2, 8, 6) if smoke else (4, 16, 12)
    max_seq = P + G
    params = M_.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                 cfg.vocab_size)

    def first_step_logits(kv_quant):
        """Prefill + one decode step; returns that step's logits (the
        tolerance-bounded part of the serving contract -- later steps
        compound through token feedback)."""
        logits, cache, pos = M_.prefill(params, prompts, cfg,
                                        max_seq=max_seq,
                                        cache_dtype=jnp.float32,
                                        kv_quant=kv_quant)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        lg, _ = M_.decode_step(params, cfg, cache, pos, tok)
        return np.asarray(lg)

    base_loop = ServeLoop(params, cfg, max_seq=max_seq)
    base_tokens = np.asarray(base_loop.run(prompts, G))
    base_summary = base_loop.summary()
    lg_ref = first_step_logits(None)
    scale = float(np.abs(lg_ref).max()) or 1.0

    out = {"config": {"arch": cfg.name, "batch": B, "prompt_len": P,
                      "gen": G},
           "f32": {"decode_tok_per_s":
                   base_summary.get("decode", {}).get("tok_per_s", 0.0)}}
    for name in QUANT_NAMES:
        loop = ServeLoop(params, cfg, max_seq=max_seq,
                         quantize_experts=name, kv_quant=name)
        gen = np.asarray(loop.run(prompts, G))
        s = loop.summary()
        lg = first_step_logits(name)
        err = float(np.abs(lg - lg_ref).max())
        out[name] = {
            "decode_tok_per_s": s.get("decode", {}).get("tok_per_s", 0.0),
            "prefill_ms": s["prefill"]["seconds"] * 1e3,
            "tokens_match_frac": float((gen == base_tokens).mean()),
            "first_decode_logit_max_abs_err": err,
            "first_decode_logit_rel_err": err / scale,
        }
    return out


def sweep(*, smoke: bool = False) -> dict:
    """The measured narrow-precision payload (BENCH_precision.json body);
    importable by the bench-tier smoke test."""
    return {
        "ladder_rows": _ladder_rows(),
        "spmm": _spmm_sweep(smoke=smoke),
        "serving": _serving_sweep(smoke=smoke),
    }


def _sweep_rows(payload: dict) -> list:
    rows = list(payload["ladder_rows"])
    for name, p in payload["spmm"]["points"].items():
        rows.append(row(
            f"precision/spmm/{name}", p["time_us"],
            f"gflops={p['gflops']:.3f};rel_err={p['rel_err']:.2e}"))
    for name in QUANT_NAMES:
        s = payload["serving"][name]
        rows.append(row(
            f"precision/serve/{name}", 0.0,
            f"decode_tok_per_s={s['decode_tok_per_s']:.1f};"
            f"tokens_match_frac={s['tokens_match_frac']:.2f};"
            f"logit_rel_err={s['first_decode_logit_rel_err']:.2e}"))
    return rows


def run() -> list:
    return _sweep_rows(sweep(smoke=True))


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    payload = sweep(smoke=args.smoke)
    rows = _sweep_rows(payload)
    payload["rows"] = rows
    path = emit_bench("precision", payload)
    print("\n".join(rows))
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
