"""Each driver end to end at a tiny size on the CPU (Pallas in interpret
mode), with the harness's look for a chip skipped: a sound run is correct,
the control and every fault the cell can have make it not correct, and
without a TPU no device metric is printed and ``bench/run.py`` refuses."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
B = json.loads((ROOT / "BENCHMARK.json").read_text())


def _spec(cell_name, config, traffic):
    cell = next(c for c in B["workloads"] if c["name"] == cell_name)
    return harness.Spec(cell, config, traffic,
                        *harness.metrics_of(B, cell_name))


def tiny_serve():
    c = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=128, vocab_size=512,
             num_hidden_layers=2)
    c["serving"].update(slots=4, max_seq=96)
    t = {"clients": 4, "prompt_len": {"choice": [8, 16]},
         "output_len": {"lognormal": {"median": 24, "sigma": 0.5},
                        "min": 8, "max": 48},
         "pool": 32, "warm_start": {"context": [8, 16, 24, 32],
                                    "remaining": [40, 36, 32, 28]}}
    return _spec("qwen3-1.7b.reasoning", c, t)


def tiny_stencil():
    c = json.loads((BENCH / "configs" / "j3d27pt.json").read_text())
    c["interior"] = 32
    t = json.loads((BENCH / "traffic" / "jacobi.json").read_text())
    return _spec("j3d27pt.jacobi", c, t)


def _run(spec, trace=False, seconds=2.0, seed=2 ** 33 + 1):
    return harness.run_cell(spec, seed, seconds, trace,
                            t_process=time.monotonic(),
                            devices=jax.devices()[:1], log=lambda *a, **k: 0)


class Keep:
    """Runs a cell as ``run_cell`` does and keeps the cell object."""

    def __init__(self, spec, seconds=2.0, seed=5):
        kind = harness.driver(spec.config["kind"])
        self.cell = kind.Cell(spec.config, spec.traffic, seed,
                              jax.devices()[:1], seconds)
        self.cell.setup()
        self.cell.run(seconds, None)
        self.cell.release()


# -------------------------------------------------------------- serving --

def test_serve_sound_run():
    spec = tiny_serve()
    out = _run(spec)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"output_tok_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    traced = _run(spec, trace=True)
    # no device metric without a TPU: only host-clock and program readings
    assert set(traced["metrics"]) == {"model.decode_step_ms",
                                      "setup.compile_s"}
    assert "busy_s" not in traced["device"] and "breakdown" not in traced


def test_serve_control_fails():
    # four layers at d 128: enough depth and tokens for the float8 control
    # to stand out as it does at full size
    spec = tiny_serve()
    spec.config.update(hidden_size=128, head_dim=32, intermediate_size=256,
                       vocab_size=2048, num_hidden_layers=4)
    spec.config["serving"]["max_seq"] = 160
    k = Keep(spec, seed=1)
    limit = spec.config["check"]["max_logit_gap"]
    assert k.cell.gaps().max() <= limit
    assert k.cell.gaps(control=True).max() > limit


def test_serve_token_altered_fails(monkeypatch):
    from repro.launch import serve as S
    real = S.ServeScheduler._sample_one

    def altered(self, logits_row, req):
        return (real(self, logits_row, req) + 1) % self.cfg.vocab_size
    monkeypatch.setattr(S.ServeScheduler, "_sample_one", altered)
    assert not _run(tiny_serve())["correct"]


def test_serve_state_unchanged_fails(monkeypatch):
    from repro.models import model as M
    real = M.decode_step

    def stale(params, cfg, cache, pos, tokens_1, *a, **k):
        logits, _ = real(params, cfg, cache, pos, tokens_1, *a, **k)
        return logits, cache                 # the KV cache never advances
    monkeypatch.setattr(M, "decode_step", stale)
    assert not _run(tiny_serve())["correct"]


# -------------------------------------------------------------- stencil --

def test_stencil_sound_run():
    spec = tiny_stencil()
    out = _run(spec)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"call_ms", "setup_s"}
    assert out["attempted"] > 16
    traced = _run(spec, trace=True)
    assert set(traced["metrics"]) == {"setup.compile_s"}


def test_stencil_control_fails():
    spec = tiny_stencil()
    k = Keep(spec)
    limit = spec.config["check"]["max_rel_error"]
    assert k.cell.errors() <= limit
    assert k.cell.errors(dtype="bfloat16") > limit


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_stencil_fault_fails(monkeypatch, fault):
    from repro.kernels.stencil import ops
    real = ops.apply

    def broken(grid_in, spec, **kw):
        out = real(grid_in, spec, **kw)
        if fault == "state_unchanged":
            return grid_in[tuple(slice(1, -1) for _ in range(grid_in.ndim))]
        return out.at[(3,) * out.ndim].add(1.0)
    monkeypatch.setattr(ops, "apply", broken)
    assert not _run(tiny_stencil())["correct"]


# ----------------------------------------------------------------- CLI --

@pytest.mark.parametrize("workload", ["j3d27pt.jacobi",
                                      "qwen3-1.7b.reasoning"])
def test_cli_refuses_without_a_tpu(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        workload, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_seed_makes_the_same_inputs():
    spec = tiny_stencil()

    def grid(seed):
        c = harness.driver("stencil").Cell(spec.config, spec.traffic, seed,
                                           jax.devices()[:1], 1.0)
        c.setup()
        return np.asarray(c.x)
    a, b, c = grid(2 ** 35), grid(2 ** 35), grid(2 ** 35 + 1)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
