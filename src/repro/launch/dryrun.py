import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax-importing module: jax locks the
# device count at first backend init. Placeholder host devices exist ONLY in
# this dry-run entrypoint; tests/benches see the single real CPU device.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Per cell:
  * builds the step function (train/prefill/serve) with production shardings,
  * ``.lower().compile()`` against ShapeDtypeStruct inputs (no allocation),
  * records memory_analysis / cost_analysis / loop-aware HLO accounting
    (FLOPs, HBM-traffic proxy, per-op collective bytes) as one JSON file.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --list-cells
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.launch import steps as St
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, cell_is_runnable, token_inputs
from repro.parallel import sharding as Sh

# v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _with_sharding(structs, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        structs, shardings,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# --variant opt: the SPerf-optimized configuration (per-cell knobs)
OPT_MICROBATCHES = {  # train_4k cells that exceed HBM at microbatch=1
    "nemotron-4-340b": 1,
    "llama4-maverick-400b-a17b": 4,
    "llama4-scout-17b-a16e": 4,
    "gemma3-12b": 4,
    "zamba2-1.2b": 2,
    "internvl2-26b": 2,
}


def build_cell(arch: str, shape_name: str, mesh, variant: str = "base"):
    """Returns (jitted, abstract_args) for the cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        opt = St.default_optimizer(master_weights=(variant == "opt"))
        kw = {}
        if variant == "opt":
            if cfg.n_experts:
                kw["moe_impl"] = "shard_map"
            kw["microbatches"] = (OPT_MICROBATCHES.get(arch, 1)
                                  if shape_name == "train_4k" else 1)
            kw["attn_impl"] = "kernel_sharded"
        step, (p_s, o_s, tok_s, emb_s), out_s = St.make_train_step(
            cfg, shape, mesh, opt, **kw)
        abs_params = St.abstract_params(cfg)
        if variant == "opt":
            abs_params = St.cast_params_bf16(abs_params)
        params = _with_sharding(abs_params, _ns(mesh, p_s))
        abs_opt = jax.eval_shape(opt.init, abs_params)
        opt_state = _with_sharding(abs_opt, _ns(mesh, o_s))
        tokens, emb = token_inputs(cfg, shape)
        tokens = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                      sharding=NamedSharding(mesh, tok_s))
        args = [params, opt_state, tokens]
        out_shardings = (_ns(mesh, out_s[0]), _ns(mesh, out_s[1]),
                         _ns(mesh, out_s[2]))
        if emb is not None:
            args.append(jax.ShapeDtypeStruct(
                emb.shape, emb.dtype, sharding=NamedSharding(mesh, emb_s)))
        jitted = jax.jit(step, out_shardings=out_shardings,
                         donate_argnums=(0, 1))
        return jitted, args, cfg, shape

    if shape.kind == "prefill":
        kw = {}
        if variant == "opt":
            kw["impl"] = "kernel_sharded"
            if cfg.n_experts:
                kw["moe_impl"] = "shard_map"
        step, (p_s, tok_s, emb_s), out_s = St.make_prefill_step(
            cfg, shape, mesh, **kw)
        params = _with_sharding(St.abstract_params(cfg), _ns(mesh, p_s))
        tokens, emb = token_inputs(cfg, shape)
        tokens = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                      sharding=NamedSharding(mesh, tok_s))
        args = [params, tokens]
        if emb is not None:
            args.append(jax.ShapeDtypeStruct(
                emb.shape, emb.dtype, sharding=NamedSharding(mesh, emb_s)))
        out_shardings = (_ns(mesh, out_s[0]), _ns(mesh, out_s[1]),
                         NamedSharding(mesh, out_s[2]))
        return jax.jit(step, out_shardings=out_shardings), args, cfg, shape

    # decode
    step, (p_s, c_s, pos_s, tok_s), out_s = St.make_serve_step(cfg, shape, mesh)
    params = _with_sharding(St.abstract_params(cfg), _ns(mesh, p_s))
    cache = _with_sharding(St.abstract_cache(cfg, shape), _ns(mesh, c_s))
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    tokens_1 = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32,
                                    sharding=NamedSharding(mesh, tok_s))
    out_shardings = (_ns(mesh, out_s[0]), None, _ns(mesh, out_s[2]))
    jitted = jax.jit(step, out_shardings=out_shardings, donate_argnums=(1,))
    return jitted, [params, cache, pos, tokens_1], cfg, shape


def model_flops(cfg, shape) -> float:
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch * 1  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             save_hlo: bool = False, variant: str = "base") -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    with jax.set_mesh(mesh):
        jitted, args, cfg, shape = build_cell(arch, shape_name, mesh, variant)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    acc = analyze(hlo)

    coll = acc["collective_bytes_total"]
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "devices": n_dev,
        "variant": variant,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device": ma.argument_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            + ma.output_size_in_bytes,
        },
        "cost_analysis": {"flops_body_once": ca.get("flops", 0.0),
                          "bytes_body_once": ca.get("bytes accessed", 0.0)},
        "hlo": {k: acc[k] for k in ("dot_flops", "collective_bytes",
                                    "collective_bytes_total",
                                    "collective_bytes_tpu_corrected",
                                    "traffic_bytes", "n_computations")},
        "op_hist": acc["op_hist"],
        "roofline": {
            "compute_s": acc["dot_flops"] / PEAK_FLOPS_BF16,
            "memory_s": acc["traffic_bytes"] / HBM_BW,
            "collective_s": coll / ICI_BW,
        },
        "model_flops_total": model_flops(cfg, shape),
        "model_flops_per_device": model_flops(cfg, shape) / n_dev,
    }
    r = rec["roofline"]
    dom = max(r, key=r.get)
    rec["roofline"]["dominant"] = dom
    rec["roofline"]["collective_s_tpu_corrected"] = (
        acc["collective_bytes_tpu_corrected"] / ICI_BW)
    rec["model_vs_hlo_flops"] = (rec["model_flops_per_device"]
                                 / max(acc["dot_flops"], 1.0))
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "" if variant == "base" else f"__{variant}"
    name = f"{arch}__{shape_name}__{mesh_kind}{suffix}"
    (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=1))
    if save_hlo:
        (out_dir / f"{name}.hlo.txt").write_text(hlo)
    return rec


def list_cells():
    cells = []
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            if cell_is_runnable(cfg, shape):
                cells.append((arch, sname))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--variant", default="base", choices=["base", "opt"])
    ap.add_argument("--list-cells", action="store_true")
    args = ap.parse_args()
    if args.list_cells:
        for arch, sname in list_cells():
            print(f"{arch} {sname}")
        return
    assert args.arch and args.shape
    try:
        rec = run_cell(args.arch, args.shape, args.mesh, Path(args.out),
                       save_hlo=args.save_hlo, variant=args.variant)
        r = rec["roofline"]
        print(f"OK {args.arch} {args.shape} {args.mesh} [{args.variant}]: "
              f"compile={rec['compile_s']}s "
              f"peak/dev={rec['memory']['peak_per_device']/2**30:.2f}GiB "
              f"compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"dominant={r['dominant']}")
    except Exception:
        print(f"FAIL {args.arch} {args.shape} {args.mesh}")
        traceback.print_exc()
        raise SystemExit(1)


if __name__ == "__main__":
    main()
