"""Qwen3-Next's hybrid block at the SMOKE size on the CPU: the program
against the plain float32 reference (``models/reference_qwen3_next.py``),
the ``gdn_decode`` Pallas kernel (interpret mode) against its oracle,
dropless top-k routing, the expert share, and ``ServeScheduler`` with both
kinds of per-row state.

Weights are seeded random; the program runs its ``f32`` policy with a
float32 cache, so program and reference differ only in the order of float32
operations (XLA's fused reductions, the kernel's sums against the oracle's
products).  Each tolerance says how far that reordering can move a value.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.kernels.gdn.ops import gdn_decode
from repro.kernels.gdn.ref import gdn_decode_ref
from repro.launch.serve import ServeScheduler
from repro.models import model as M
from repro.models import moe
from repro.models import reference_qwen3_next as ref
from repro.runtime import resilience as R

CFG = dataclasses.replace(get_smoke("qwen3-next-80b-a3b"), policy="f32")
MAX_SEQ = 24


@pytest.fixture(scope="module")
def params():
    return jax.jit(M.init_params, static_argnums=1)(jax.random.PRNGKey(0),
                                                    CFG)


_moe = jax.jit(moe.apply_moe_dropless, static_argnums=2)


@pytest.fixture(scope="module")
def decode_jit():
    return jax.jit(lambda p, c, pos, t: M.decode_step(p, CFG, c, pos, t))


def test_smoke_keeps_the_published_shape_rules():
    """SMOKE is CONFIG's layer pattern and options at small widths."""
    full = get_config("qwen3-next-80b-a3b")
    assert CFG.block_unit == full.block_unit == ("gdn+moe",) * 3 + (
        "attn+moe",)
    for f in ("attn_output_gate", "rope_fraction", "moe_dropless",
              "moe_shared_gate", "qk_norm", "tie_embeddings"):
        assert getattr(CFG, f) == getattr(full, f), f
    assert full.n_held == 16 and full.n_experts == 512 and full.top_k == 10
    # 1.315 B parameters held at the benchmark's 8 layers (two periods)
    assert dataclasses.replace(full, n_repeats=2).param_count() == 1315424384


def test_prefill_then_decode_matches_reference(params, decode_jit):
    """Prefill logits, then every decode step through the cache (the
    kernel's state, the conv tail, the KV cache of the gated attention
    layer), against the reference's full forward pass over the same tokens.
    Tolerance 2e-4 absolute on logits of size ~1: float32 reordering over
    four layers and a 256-way unembedding stays near 1e-5; a missing gate,
    RoPE half or expert moves logits by 1e-2 or more."""
    rng = np.random.default_rng(1)
    seq = rng.integers(0, CFG.vocab_size, 14).astype(np.int32)
    want = np.asarray(jax.jit(ref.logits, static_argnums=2)(params, seq,
                                                            CFG))
    P = 9
    lg, cache, pos = M.prefill(params, jnp.asarray(seq[None, :P]), CFG,
                               max_seq=MAX_SEQ, cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(lg[0, 0, :CFG.vocab_size]),
                               want[P - 1], atol=2e-4, rtol=0)
    for t in range(P, len(seq)):
        lg, cache = decode_jit(params, cache, jnp.asarray([t], jnp.int32),
                               jnp.asarray(seq[None, t:t + 1]))
        np.testing.assert_allclose(np.asarray(lg[0, 0, :CFG.vocab_size]),
                                   want[t], atol=2e-4, rtol=0)


@pytest.mark.parametrize("B,H,D", [(2, 3, 16), (3, 2, 128)])
def test_gdn_decode_kernel_matches_oracle(B, H, D):
    """The Pallas kernel (interpret mode) against ``kernels/gdn/ref.py``,
    decays from forgetting (g = -5) to keeping (g = -1e-3).  Tolerance 1e-5
    relative to the state's scale: both are float32, the kernel sums where
    the oracle contracts."""
    rng = np.random.default_rng(D)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = n(B, H, D), n(B, H, D), n(B, H, D)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(5.0), (B, H))),
                     jnp.float32)
    beta = jax.nn.sigmoid(n(B, H))
    S = n(B, H, D, D)
    o, S1 = gdn_decode(q, k, v, g, beta, S, interpret=True)
    o_ref, S1_ref = gdn_decode_ref(q, k, v, g, beta, S)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S1_ref),
                               atol=1e-5 * D ** 0.5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=1e-5 * D, rtol=1e-5)


def test_topk_routing_drops_nothing(params):
    """Every token gets exactly top_k distinct experts with weights summing
    to 1, however skewed the router: a router that sends every token to the
    same experts still computes every (token, expert) pair.  With every
    expert held, the pair count is B * S * top_k."""
    cfg = dataclasses.replace(CFG, experts_held=None)
    d, E = cfg.d_model, cfg.n_experts
    p = jax.jit(moe.init_moe, static_argnums=1)(jax.random.PRNGKey(3), cfg)
    # skew: experts 0..3 win for every token by a wide margin
    p["router"] = p["router"].at[:, :4].add(0.5)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 40, d)) + 1.0
    w, ids = moe.route_topk(p["router"], x, cfg)
    assert ids.shape == (3, 40, cfg.top_k)
    assert np.all(np.sort(np.asarray(ids), -1)[..., 1:]
                  != np.sort(np.asarray(ids), -1)[..., :-1])
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    out, pairs = _moe(p, x, cfg)
    np.testing.assert_array_equal(np.asarray(pairs), 40 * cfg.top_k)
    # no token is lost: each equals its own top-k mixture, computed alone
    # (router logits near 30 carry ~3e-6 of float32 rounding, which the
    # renormalised weights pass on)
    for b, s in ((0, 0), (2, 39)):
        one, _ = _moe(p, x[b:b + 1, s:s + 1], cfg)
        np.testing.assert_allclose(np.asarray(one[0, 0]),
                                   np.asarray(out[b, s]), atol=2e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The held parts of every share of the experts, plus the shared expert
    counted once, equal the layer that holds them all (and the reference's
    MoE).  Tolerance 1e-5: float32 sums in another order."""
    full = dataclasses.replace(CFG, experts_held=None)
    p = jax.jit(moe.init_moe, static_argnums=1)(jax.random.PRNGKey(5), full)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 7, full.d_model))
    want, _ = _moe(p, x, full)
    no_shared = dataclasses.replace(full, moe_shared_expert=False)
    shared = want - _moe(p, x, no_shared)[0]
    held = 8
    total, pairs = shared, 0
    for off in range(0, full.n_experts, held):
        share = dataclasses.replace(no_shared, experts_held=held,
                                    expert_offset=off)
        ps = dict(p, experts={k: v[off:off + held]
                              for k, v in p["experts"].items()})
        part, n = _moe(ps, x, share)
        total, pairs = total + part, pairs + n
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(pairs), 7 * full.top_k)
    np.testing.assert_allclose(
        np.asarray(want[1]), np.asarray(ref.moe(p, x[1], full)), atol=1e-5,
        rtol=1e-4)


def test_scheduler_evicts_and_admits_both_state_kinds(params):
    """Two slots, three requests of mixed lengths: rows are evicted and
    refilled (KV cache, conv tail, recurrent state and the held-pair leaf
    scattered), and every request's tokens are identical to serving it
    alone (a one-slot pool serves them one after another).  Each decode
    tick makes one host sync and records its held (token, expert) pairs."""
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, CFG.vocab_size, int(n)), int(g))
            for n, g in ((5, 6), (9, 3), (4, 5))]

    def serve(slots):
        s = ServeScheduler(params, CFG, max_seq=MAX_SEQ, max_slots=slots,
                           cache_dtype=jnp.float32)
        for prompt, gen in reqs:
            s.submit(prompt, gen)
        return s, s.run()

    alone = serve(1)[1]
    sched, got = serve(2)
    assert sched.n_slots == 2 and len(got) == len(alone) == 3
    for uid, toks in got.items():
        np.testing.assert_array_equal(toks, alone[uid])
    ticks = [s for s in sched.stats if s.phase == "step"]
    decodes = [s for s in sched.stats if s.phase == "decode"]
    assert decodes and all(s.extra["moe_held_pairs"] > 0 for s in decodes)
    admits = {s.step for s in sched.stats if s.phase == "prefill"}
    assert all(t.extra["host_syncs"] == 1 for t in ticks
               if t.step not in admits)


def test_sample_fault_retries_without_a_second_forward(params):
    """A sample-stage exception fires after the tick's donated forward has
    advanced every row's recurrent state and held-pair leaf.  The retry
    samples again from the logits in hand, so the tokens and the pool left
    behind equal a fault-free run's; a second forward would advance the
    Gated DeltaNet state twice."""
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, CFG.vocab_size, n), 6) for n in (7, 5)]

    def serve(plan):
        s = ServeScheduler(params, CFG, max_seq=MAX_SEQ, max_slots=2,
                           cache_dtype=jnp.float32, fault_plan=plan)
        for prompt, gen in reqs:
            s.submit(prompt, gen)
        return s, s.run()

    base_s, base = serve(None)
    plan = R.FaultPlan.single("sample", "exception", step=2)
    sched, got = serve(plan)
    assert plan.triggered and not sched.failed
    assert sched.health.counters["retry"] == 1
    assert sorted(got) == sorted(base) == [0, 1]
    for uid, toks in base.items():
        np.testing.assert_array_equal(got[uid], toks)
    for a, b in zip(jax.tree.leaves(sched.cache),
                    jax.tree.leaves(base_s.cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
