"""Tier-1 MoE routing tests: prefix-stable slots, decode == prefill.

The contract under test (see models/moe.py): a token's expert slot and
keep/drop decision are pure functions of its own row's routing history --
never of batch companions or of tokens that come later.  Stepwise decode
(counts threaded through the cache) must therefore reproduce the prefill
drop set *bit-identically*, for both dispatch backends.

These run on a tiny config with capacity_factor=1.0 so drops actually
happen (the old in-batch-cumsum formulation fails all of these).
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ArchConfig
from repro.models import model as M
from repro.models import moe

TINY = ArchConfig(
    name="tiny-moe", family="moe", d_model=32, n_heads=2, n_kv_heads=1,
    d_ff=48, vocab_size=64, block_unit=("attn+moe",), n_repeats=2,
    head_dim=16, n_experts=4, top_k=1, capacity_factor=1.0,
    moe_shared_expert=True, policy="f32")

KEY = jax.random.PRNGKey(0)
BACKENDS = ("gather", "bcsr")


def _layer():
    p = moe.init_moe(KEY, TINY)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, TINY.d_model),
                          jnp.float32)
    return p, x


# ------------------------------------------------------------- routing law --

def test_prefix_capacity_is_ceil():
    # documented law: C(t) = ceil((t+1)/E * f).  int() truncation would give
    # 3 at t=9 (10 * 1.25 / 4 = 3.125) -- the old off-by-one drop.
    assert int(moe.prefix_capacity(9, 4, 1.25)) == 4
    assert int(moe.prefix_capacity(0, 4, 1.0)) == 1
    assert int(moe.prefix_capacity(7, 4, 1.0)) == 2
    # dispatch buffer bound uses the same arithmetic and never under-sizes
    assert moe.dispatch_capacity(10, dataclasses.replace(TINY,
                                                         capacity_factor=1.25)) == 4


def test_routing_is_prefix_stable_stepwise():
    """Routing all S tokens at once == one token at a time with counts
    carried -- slots, keep sets, and final occupancy all bit-identical."""
    p, x = _layer()
    full = moe.route_tokens(p["router"], x, TINY)
    assert int((~full.keep).sum()) > 0, "test config must actually drop"
    counts = None
    keeps, slots, experts = [], [], []
    for t in range(x.shape[1]):
        r = moe.route_tokens(p["router"], x[:, t:t + 1], TINY,
                             counts=counts, pos0=t)
        counts = r.new_counts
        keeps.append(r.keep[:, 0])
        slots.append(r.slot[:, 0])
        experts.append(r.expert_id[:, 0])
    np.testing.assert_array_equal(np.stack(experts, 1),
                                  np.asarray(full.expert_id))
    np.testing.assert_array_equal(np.stack(slots, 1), np.asarray(full.slot))
    np.testing.assert_array_equal(np.stack(keeps, 1), np.asarray(full.keep))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(full.new_counts))


def test_routing_ignores_batch_companions():
    """A row's decisions must not depend on which rows share the batch."""
    p, x = _layer()
    full = moe.route_tokens(p["router"], x, TINY)
    solo = moe.route_tokens(p["router"], x[1:2], TINY)
    np.testing.assert_array_equal(np.asarray(full.keep[1]),
                                  np.asarray(solo.keep[0]))
    np.testing.assert_array_equal(np.asarray(full.slot[1]),
                                  np.asarray(solo.slot[0]))


# ------------------------------------------------------------ layer parity --

@pytest.mark.parametrize("dispatch", BACKENDS)
def test_apply_moe_decode_matches_prefill(dispatch):
    p, x = _layer()
    full, full_counts = moe.apply_moe(p, x, TINY, dispatch=dispatch)
    counts, outs = None, []
    for t in range(x.shape[1]):
        o, counts = moe.apply_moe(p, x[:, t:t + 1], TINY, counts=counts,
                                  pos=jnp.asarray(t, jnp.int32),
                                  dispatch=dispatch)
        outs.append(o[:, 0])
    step = jnp.stack(outs, axis=1)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(full_counts))
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               atol=1e-6, rtol=1e-6)


def test_dispatch_backends_bit_identical():
    """The BCSR path multiplies by exact 0/1 blocks with f32 accumulation,
    so both backends must produce the same bits (swap-safe mid-deployment)."""
    p, x = _layer()
    g, _ = moe.apply_moe(p, x, TINY, dispatch="gather")
    b, _ = moe.apply_moe(p, x, TINY, dispatch="bcsr")
    np.testing.assert_array_equal(np.asarray(g), np.asarray(b))
    # and under tracing (full-grid index stream), bit for bit against the
    # traced gather layer.  Traced and eager layers agree only to an ulp:
    # under jit XLA fuses the combine's gate multiply with the shared
    # expert's residual add into one loop, computed with a single rounding.
    gj, bj = (jax.jit(lambda p, x, d=d: moe.apply_moe(p, x, TINY,
                                                      dispatch=d)[0])(p, x)
              for d in BACKENDS)
    np.testing.assert_array_equal(np.asarray(gj), np.asarray(bj))
    np.testing.assert_allclose(np.asarray(bj), np.asarray(g),
                               rtol=1e-6, atol=1e-6)


def test_moe_group_misalignment_warns_and_strict_raises():
    p, x = _layer()  # B = 2
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        moe.apply_moe(p, x, TINY, groups=3)
    assert any(issubclass(i.category, RuntimeWarning) for i in w)
    with pytest.raises(ValueError):
        moe.apply_moe(p, x,
                      dataclasses.replace(TINY, moe_strict_dispatch=True),
                      groups=3)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # G | B: no warning
        moe.apply_moe(p, x, TINY, groups=2)


# ------------------------------------------------------------ model parity --

@pytest.mark.parametrize("dispatch", BACKENDS)
def test_model_decode_matches_prefill_tiny(dispatch):
    """Full-model parity on the tiny config, capacity drops active, both
    dispatch backends.  f32 policy + prefix-aligned decode arithmetic make
    this near-exact, so the tolerance is tight."""
    cfg = dataclasses.replace(TINY, moe_dispatch=dispatch)
    params = M.init_params(KEY, cfg)
    B, S = 1, 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                cfg.vocab_size)
    full = M.forward(params, tokens, cfg)
    cache = M.init_cache(cfg, batch=B, max_seq=S, dtype=jnp.float32)
    outs = []
    for t in range(S):
        logits, cache = M.decode_step(params, cfg, cache,
                                      jnp.asarray(t, jnp.int32),
                                      tokens[:, t:t + 1], dtype=jnp.float32)
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               atol=1e-4, rtol=1e-4)


def test_prefill_carries_routing_counts_into_decode():
    """prefill(prompt) -> decode must continue each expert queue where the
    prompt left it: the cache carries per-(row, expert) occupancy."""
    cfg = TINY
    params = M.init_params(KEY, cfg)
    B, S_prompt, S_gen = 1, 6, 4
    S = S_prompt + S_gen
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0,
                                cfg.vocab_size)
    full = M.forward(params, tokens, cfg)
    logits, cache, pos = M.prefill(params, tokens[:, :S_prompt], cfg,
                                   max_seq=S, cache_dtype=jnp.float32)
    counts = cache["slots"][0]["moe"]
    assert counts.shape == (cfg.n_repeats, B, cfg.n_experts)
    assert counts.dtype == jnp.int32
    # every routed prompt token is counted, kept or dropped
    assert int(counts.sum()) == cfg.n_repeats * B * S_prompt
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, S_prompt - 1]),
                               atol=1e-4, rtol=1e-4)
    outs = []
    for t in range(S_prompt, S):
        step_logits, cache = M.decode_step(params, cfg, cache,
                                           jnp.asarray(t, jnp.int32),
                                           tokens[:, t:t + 1],
                                           dtype=jnp.float32)
        outs.append(step_logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, S_prompt:]),
                               atol=1e-4, rtol=1e-4)
