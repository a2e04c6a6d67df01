"""ServeLoop: the static-batch serving loop.

Tier-1 coverage runs on a tiny MoE config (seconds, CPU): token-for-token
parity of the ServeLoop against the plain prefill-then-decode loop (fused
jit decode) and reproducible temperature sampling.  The full smoke-arch
loop on both dispatch backends is ``@pytest.mark.serve`` -- tiered out of
the default selection like ``slow`` (enable with ``--run-serve`` or
``-m serve``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ArchConfig
from repro.models import model as M
from repro.launch.serve import ServeLoop

TINY = ArchConfig(
    name="tiny-serve", family="moe", d_model=32, n_heads=2, n_kv_heads=1,
    d_ff=48, vocab_size=64, block_unit=("attn", "attn+moe"), n_repeats=2,
    head_dim=16, n_experts=4, top_k=1, capacity_factor=1.0,
    moe_shared_expert=True, policy="f32")

B, PROMPT, GEN = 2, 8, 6
MAX_SEQ = PROMPT + GEN


@pytest.fixture(scope="module")
def tiny_model():
    params = M.init_params(jax.random.PRNGKey(0), TINY)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0,
                                 TINY.vocab_size)
    return params, prompts


def _old_style_loop(params, cfg, prompts, gen):
    """The pre-ServeLoop smoke loop, verbatim semantics: jit fused decode,
    greedy argmax."""
    logits, cache, pos = M.prefill(params, prompts, cfg, max_seq=MAX_SEQ)
    nxt = jnp.argmax(logits[:, -1, :cfg.vocab_size],
                     axis=-1)[:, None].astype(jnp.int32)
    decode = jax.jit(lambda p, c, pos, tok: M.decode_step(p, cfg, c, pos, tok))
    toks = [nxt]
    for i in range(gen - 1):
        lg, cache = decode(params, cache, pos + i, nxt)
        nxt = jnp.argmax(lg[:, -1, :cfg.vocab_size],
                         axis=-1)[:, None].astype(jnp.int32)
        toks.append(nxt)
    return np.asarray(jnp.concatenate(toks, axis=1))


def test_serve_loop_fused_matches_old_loop(tiny_model):
    """ServeLoop in fused mode is token-for-token the old serving loop."""
    params, prompts = tiny_model
    want = _old_style_loop(params, TINY, prompts, GEN)
    loop = ServeLoop(params, TINY, max_seq=MAX_SEQ)
    got = loop.run(prompts, GEN)
    np.testing.assert_array_equal(got, want)
    s = loop.summary()
    assert s["decode"]["calls"] == GEN - 1
    assert s["prefill"]["seconds"] > 0 and s["decode"]["seconds"] > 0


def test_serve_loop_temperature_sampling_runs(tiny_model):
    """Temperature > 0 exercises the categorical path deterministically
    (fixed sample_seed): same loop twice = same tokens."""
    params, prompts = tiny_model
    a = ServeLoop(params, TINY, max_seq=MAX_SEQ, temperature=0.7,
                  sample_seed=7).run(prompts, GEN)
    b = ServeLoop(params, TINY, max_seq=MAX_SEQ, temperature=0.7,
                  sample_seed=7).run(prompts, GEN)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (B, GEN)


@pytest.mark.serve
@pytest.mark.parametrize("dispatch", ["gather", "bcsr"])
def test_serve_loop_smoke_arch(dispatch):
    """Full smoke-config serving loop on a real MoE arch, both backends.
    Tiered behind --run-serve."""
    from repro.configs import get_smoke

    cfg = get_smoke("llama4-scout-17b-a16e")
    cfg = dataclasses.replace(cfg, moe_dispatch=dispatch)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    loop = ServeLoop(params, cfg, max_seq=16)
    gen = loop.run(prompts, 4)
    assert gen.shape == (2, 4)
    assert (gen >= 0).all() and (gen < cfg.vocab_size).all()
