"""ServeScheduler: continuous-batching multi-tenant serving.

Tier-1 coverage on tiny configs (seconds, CPU).  The load-bearing contract
is **composition independence**: a request's generated tokens must not
depend on which neighbours share the batch, when it was admitted, or which
slot it landed in -- so a join/evict schedule with staggered arrivals is
token-identical to running each request alone through a sequential
``ServeLoop`` with the request's own key chain: on a dense stack, a top-1
MoE stack with gather dispatch and with the bcsr SpMM traced into the fused
tick, and the hybrid Qwen3-Next stack, greedy and at temperature 0.7.
Plus the serving-state correctness checks: KV-cache overflow raises
instead of silently clamping, seeded ``run()`` calls are bit-identical,
and the batch-bucket law bounds the compiled step shapes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import engine
from repro.models.config import ArchConfig
from repro.models import model as M
from repro.launch.serve import ServeLoop, ServeScheduler

from test_serve_inplace import DENSE, QWEN3_NEXT, reuse_programs

TINY = ArchConfig(
    name="tiny-serve", family="moe", d_model=32, n_heads=2, n_kv_heads=1,
    d_ff=48, vocab_size=64, block_unit=("attn", "attn+moe"), n_repeats=2,
    head_dim=16, n_experts=4, top_k=1, capacity_factor=1.0,
    moe_shared_expert=True, policy="f32")

MAX_SEQ = 24
SEED = 3

# (config, dispatch, temperature) per parity case; the greedy top-1 MoE
# cases keep their dispatch-only ids
PARITY = {
    "gather": ("moe", "gather", 0.0),
    "bcsr": ("moe", "bcsr", 0.0),
    "dense": ("dense", "gather", 0.0),
    "qwen3-next": ("qwen3-next", "gather", 0.0),
    "gather-t0.7": ("moe", "gather", 0.7),
    "bcsr-t0.7": ("moe", "bcsr", 0.7),
    "dense-t0.7": ("dense", "gather", 0.7),
    "qwen3-next-t0.7": ("qwen3-next", "gather", 0.7),
}
CONFIGS = {"moe": TINY, "dense": DENSE, "qwen3-next": QWEN3_NEXT}


def _trace(cfg):
    """Mixed prompt/generation lengths: the trace that forces join/evict."""
    rng = np.random.default_rng(0)
    return [(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 10))),
             int(rng.integers(3, 8))) for _ in range(5)]


@pytest.fixture(scope="module")
def tiny_model():
    return M.init_params(jax.random.PRNGKey(0), TINY), _trace(TINY)


@pytest.fixture(scope="module")
def models(tiny_model):
    """``models(name) -> (params, reqs)``, each built on first use."""
    built = {"moe": tiny_model}

    def get(name):
        if name not in built:
            cfg = CONFIGS[name]
            built[name] = (jax.jit(M.init_params, static_argnums=1)(
                jax.random.PRNGKey(0), cfg), _trace(cfg))
        return built[name]
    return get


def _sequential_reference(params, cfg, reqs, dispatch, temperature):
    """Each request alone through a sequential ServeLoop (same max_seq, so
    the decode cache geometry matches the scheduler's slot rows), sampling
    from the key chain the scheduler gives request ``uid``."""
    loop = reuse_programs(ServeLoop(params, cfg, max_seq=MAX_SEQ,
                                    dispatch=dispatch,
                                    temperature=temperature))
    out = []
    for uid, (prompt, gen) in enumerate(reqs):
        key = jax.random.fold_in(jax.random.PRNGKey(SEED), uid)
        out.append(loop.run(jnp.asarray(prompt[None, :], jnp.int32), gen,
                            sample_key=key)[0])
    return out


@pytest.mark.parametrize("case", list(PARITY))
def test_scheduler_matches_sequential(models, case):
    """Continuous batching with staggered arrivals, join/evict, and a slot
    pool smaller than the request count is token-identical per request to
    sequential single-request serving."""
    name, dispatch, temperature = PARITY[case]
    cfg = CONFIGS[name]
    params, reqs = models(name)
    want = _sequential_reference(params, cfg, reqs, dispatch, temperature)

    sched = reuse_programs(ServeScheduler(
        params, cfg, max_seq=MAX_SEQ, max_slots=2, dispatch=dispatch,
        temperature=temperature, sample_seed=SEED))
    for prompt, gen in reqs[:3]:
        sched.submit(prompt, gen)
    late_submitted = False
    while sched.has_work():
        sched.step()
        if sched.step_idx == 2 and not late_submitted:
            for prompt, gen in reqs[3:]:     # arrivals mid-flight
                sched.submit(prompt, gen)
            late_submitted = True
    gen_map = sched.run()   # drains nothing further; returns uid -> tokens
    assert len(gen_map) == len(reqs)
    for uid, tokens in gen_map.items():
        np.testing.assert_array_equal(tokens, want[uid])
        assert len(tokens) == reqs[uid][1]

    # the pool saturated (2 slots, 5 requests): evictions freed slots that
    # later admissions reused
    assert any(s.extra.get("active") == 2 for s in sched.stats
               if s.phase == "decode")
    prefills = [s for s in sched.stats if s.phase == "prefill"]
    assert len(prefills) == len(reqs)


def test_scheduler_batch_bucket_law(tiny_model, monkeypatch):
    """Decode-step batch shapes are power-of-two buckets, and the fused
    tick (bcsr dispatch traced in) is traced once per bucket, never once
    per batch-composition change."""
    params, reqs = tiny_model
    traces = []
    decode_step = M.decode_step
    monkeypatch.setattr(M, "decode_step", lambda *a, **k: traces.append(
        a[3].shape) or decode_step(*a, **k))
    sched = ServeScheduler(params, TINY, max_seq=MAX_SEQ, max_slots=3,
                           dispatch="bcsr")
    # allocation is itself bucketed: 3 requested slots -> 4 rows
    assert sched.n_slots == 4
    for prompt, gen in reqs:
        sched.submit(prompt, gen)
    sched.run()
    assert sched.batch_buckets <= {1, 2, 4}
    for s in sched.stats:
        if s.phase == "decode":
            b = s.extra["batch_bucket"]
            assert b == engine.batch_bucket(b)   # a fixed point = a pow2
            assert s.extra["active"] <= b
    summ = sched.summary()
    assert sorted(traces) == [(b,) for b in summ["batch_buckets"]]
    assert summ["decode"]["tok_per_s"] > 0
    assert summ["token_latency_ms"]["p50"] <= summ["token_latency_ms"]["p99"]


def test_scheduler_eos_eviction(tiny_model):
    """A request whose next token is its eos_id evicts immediately and
    frees the slot for the queue."""
    params, reqs = tiny_model
    prompt, gen = reqs[0]
    # find the first greedy token, then use it as the eos of a second run
    probe = reuse_programs(ServeScheduler(params, TINY, max_seq=MAX_SEQ,
                                          max_slots=1))
    probe.submit(prompt, 4)
    first = probe.run()[0][0]

    sched = reuse_programs(ServeScheduler(params, TINY, max_seq=MAX_SEQ,
                                          max_slots=1))
    sched.submit(prompt, 4, eos_id=int(first))
    sched.submit(reqs[1][0], 2)
    out = sched.run()
    assert len(out[0]) == 1 and out[0][0] == first   # stopped at eos
    assert len(out[1]) == 2                          # queued request served


def test_scheduler_overflow_guard(tiny_model):
    """Admission refuses requests that could never fit; the decode-step
    guard is the backstop for direct state corruption."""
    params, _ = tiny_model
    sched = ServeScheduler(params, TINY, max_seq=10, max_slots=1)
    with pytest.raises(ValueError, match="never be served"):
        sched.submit(np.arange(8, dtype=np.int32), 8)
    # corrupt the state by hand to prove the decode-step backstop fires
    req = sched.submit(np.arange(4, dtype=np.int32), 2)
    sched.admit()
    req.pos = sched.max_seq
    with pytest.raises(RuntimeError, match="KV-cache overflow"):
        sched.decode_step()


def test_scheduler_temperature_reproducible(tiny_model):
    """Per-request sampling keys: the same trace served twice (even with a
    different slot pool, hence different batch composition) generates
    bit-identical tokens per request."""
    params, reqs = tiny_model

    def serve(max_slots):
        sched = reuse_programs(ServeScheduler(
            params, TINY, max_seq=MAX_SEQ, max_slots=max_slots,
            temperature=0.7, sample_seed=11))
        for prompt, gen in reqs:
            sched.submit(prompt, gen)
        return sched.run()

    a, b, c = serve(2), serve(2), serve(4)
    for uid in a:
        np.testing.assert_array_equal(a[uid], b[uid])
        np.testing.assert_array_equal(a[uid], c[uid])


def test_vector_pos_decode_matches_scalar(tiny_model):
    """The per-row-position decode path (what the scheduler drives) is
    bit-identical to the scalar path when every row sits at the same
    position -- scalar and vector pos are the same function."""
    params, _ = tiny_model
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 TINY.vocab_size)
    logits, cache, pos = M.prefill(params, prompts, TINY, max_seq=MAX_SEQ,
                                   cache_dtype=jnp.float32)
    tok = jnp.argmax(logits[:, -1, :TINY.vocab_size],
                     axis=-1)[:, None].astype(jnp.int32)
    want, want_cache = M.decode_step(params, TINY, cache, int(pos), tok)
    pos_vec = np.full((2,), int(pos), np.int32)
    got, got_cache = M.decode_step(params, TINY, cache, pos_vec, tok)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        got_cache, want_cache)
