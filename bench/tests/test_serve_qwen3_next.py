"""The Qwen3-Next serving driver end to end at a tiny size on the CPU (Pallas
in interpret mode), with the harness's look for a chip skipped: a sound run
is correct, and each planted fault -- the held experts' output zeroed, the
attention output gate skipped, the Gated DeltaNet state kept in bfloat16 --
makes it not correct, as does the float8 control."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.test_drivers import BENCH, _spec

CELL = "qwen3-next-80b-a3b.longgen"
# what the tiny size separates: the sound run reads below every limit, each
# fault above one (the cell's own limits are set on the chip at full size)
LIMITS = {"max_logit_gap": 1.0, "max_row_error": 0.3,
          "state_narrow_share": 0.01}


def tiny():
    c = json.loads((BENCH / "configs" / "qwen3-next-80b-a3b.json")
                   .read_text())
    c.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=32, linear_value_head_dim=32,
             moe_intermediate_size=64, shared_expert_intermediate_size=64,
             num_experts=4, num_experts_per_tok=4, vocab_size=1024,
             num_hidden_layers=4)
    c["deployment"]["router_experts"] = 16
    c["serving"].update(slots=4, max_seq=160)
    c["check"].update(LIMITS)
    t = {"clients": 4, "prompt_len": {"choice": [16, 24]},
         "output_len": {"lognormal": {"median": 64, "sigma": 0.5},
                        "min": 32, "max": 96},
         "pool": 16, "warm_start": {"context": [16, 32, 48, 64],
                                    "remaining": [96, 80, 72, 64]}}
    return _spec(CELL, c, t)


def _run(spec, seconds=4.0, seed=2 ** 33 + 5):
    return harness.run_cell(spec, seed, seconds, False,
                            t_process=time.monotonic(),
                            devices=jax.devices()[:1], log=lambda *a, **k: 0)


def test_sound_run():
    out = _run(tiny())
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"output_tok_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert out["failed"] == 0 and out["attempted"] >= 4


def test_control_fails():
    spec = tiny()
    kind = harness.driver(spec.config["kind"])
    cell = kind.Cell(spec.config, spec.traffic, 3, jax.devices()[:1], 4.0)
    cell.setup()
    cell.run(4.0, None)
    cell.release()
    gap, err = cell.readings()
    assert gap <= LIMITS["max_logit_gap"] and err <= LIMITS["max_row_error"]
    qgap, _ = cell.readings(quant=True)
    assert qgap > LIMITS["max_logit_gap"]


@pytest.mark.parametrize("fault", ["experts_zeroed", "gate_skipped",
                                   "state_bf16"])
def test_fault_fails(monkeypatch, fault):
    from repro.models import layers as L
    from repro.models import model as M
    from repro.models import moe
    if fault == "experts_zeroed":
        real = moe._expert_ffn
        monkeypatch.setattr(moe, "_expert_ffn",
                            lambda *a: jnp.zeros_like(real(*a)))
    elif fault == "gate_skipped":
        real = L._qkv_gate
        monkeypatch.setattr(L, "_qkv_gate",
                            lambda *a: real(*a)[:3] + (None,))
    else:
        real = M.init_cache

        def bf16_state(*a, **k):
            return jax.tree_util.tree_map_with_path(
                lambda p, x: x.astype(jnp.bfloat16)
                if getattr(p[-1], "key", None) == "state" else x,
                real(*a, **k))
        monkeypatch.setattr(M, "init_cache", bf16_state)
    out = _run(tiny())
    assert not out["correct"], out["checks"]
