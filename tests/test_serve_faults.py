"""Faults on the fused, donated decode tick of the stacks the benchmark
serves: the dense decoder and Qwen3-Next's hybrid block (Gated DeltaNet
conv tail and recurrent state beside the KV cache), plus the top-1 MoE
stack for the post-forward retry.

* **Survivor bit-identity.**  One injected fault at a time (prefill and
  sample stages, every kind; quantize on an int8 KV cache): every request
  that survives emits exactly the fault-free run's tokens.  A poisoned row
  fails only its request, and its slot row is blanked.
* **One forward per tick.**  A sample-stage exception fires after
  ``jit_decode_step`` advanced the donated pool; the retry samples again
  from the logits in hand, so the forward runs once and the pool left
  behind is the fault-free one.
* **Exhaustion.**  A tick that runs out of retries raises and leaves the
  pool its forward advanced in ``.cache``.
Tier-1, tiny configs on the CPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.serve import ServeScheduler
from repro.models import model as M
from repro.runtime import resilience as R

from test_serve_inplace import DENSE, MOE, QWEN3_NEXT, reuse_programs

CONFIGS = {"dense": DENSE, "moe": MOE, "qwen3-next": QWEN3_NEXT}
MAX_SEQ, SLOTS = 24, 2
GENS = (6, 3, 5)          # uid 1 finishes first, uid 2 takes its slot


@pytest.fixture(scope="module")
def models():
    """``models(name) -> (params, prompts)``, each built on first use."""
    built = {}

    def get(name):
        if name not in built:
            cfg = CONFIGS[name]
            rng = np.random.default_rng(5)
            built[name] = (
                jax.jit(M.init_params, static_argnums=1)(
                    jax.random.PRNGKey(0), cfg),
                [rng.integers(0, cfg.vocab_size, int(rng.integers(4, 10)))
                 for _ in GENS])
        return built[name]
    return get


def _sched(models, name, **kw):
    params, prompts = models(name)
    sched = reuse_programs(ServeScheduler(
        params, CONFIGS[name], max_seq=MAX_SEQ, max_slots=SLOTS,
        cache_dtype=jnp.float32, **kw))
    for prompt, gen in zip(prompts, GENS):
        sched.submit(prompt, gen)
    return sched


@pytest.fixture(scope="module")
def baselines(models):
    """``baselines(name, kv_quant) -> (scheduler, tokens)`` of the
    fault-free run, each made on first use."""
    runs = {}

    def get(name, kv_quant=None):
        if (name, kv_quant) not in runs:
            sched = _sched(models, name, kv_quant=kv_quant)
            runs[name, kv_quant] = sched, sched.run()
        return runs[name, kv_quant]
    return get


def _assert_pools_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# (stage, kind, selector-kwargs, kv_quant): uids 0 and 1 are resident from
# tick 0, uid 2 from the tick after uid 1 finishes
FAULTS = [
    ("prefill", "nan", dict(uid=1), None),
    ("prefill", "inf", dict(uid=2), None),
    ("prefill", "exception", dict(uid=0), None),
    ("sample", "nan", dict(uid=0, step=1), None),
    ("sample", "inf", dict(uid=2, step=4), None),
    ("sample", "exception", dict(step=2), None),
    ("sample", "straggler", dict(step=1, delay_s=0.0), None),
]
QUANTIZE = [
    ("quantize", "nan", dict(uid=0, step=1), "int8"),
    ("quantize", "inf", dict(uid=1, step=0), "int8"),
]
MATRIX = ([("qwen3-next",) + f for f in FAULTS]
          + [("dense",) + f for f in FAULTS + QUANTIZE])


@pytest.mark.parametrize("name,stage,kind,sel,kvq", MATRIX,
                         ids=[f"{n}-{s}-{k}" for n, s, k, _, _ in MATRIX])
def test_fault_matrix(models, baselines, name, stage, kind, sel, kvq):
    plan = R.FaultPlan.single(stage, kind, **sel)
    sched = _sched(models, name, kv_quant=kvq, fault_plan=plan)
    out = sched.run()
    assert plan.triggered, "fault never fired -- dead test"
    failed = {r.uid for r in sched.failed}
    base_sched, base = baselines(name, kvq)
    if kind in ("exception", "straggler") or stage == "prefill":
        # a retried prefill or tick, or a stalled one: nobody fails, and
        # the pool left behind is the fault-free run's
        assert not failed and sorted(out) == sorted(base)
        _assert_pools_equal(sched.cache, base_sched.cache)
    else:
        assert len(failed) == 1, "activation poison must fail its request"
        assert failed == {sel["uid"]}
    for uid, toks in base.items():
        if uid not in failed:
            np.testing.assert_array_equal(out[uid], toks)
    assert sched.summary()["health"]["faults_triggered"] == plan.triggered


def test_blank_cache_row_zeroes_only_that_hybrid_row():
    """Blanking a poisoned row of a hybrid pool zeroes that row of every
    leaf -- the Gated DeltaNet conv tail and recurrent state, the KV cache
    and the held-pair counts -- and leaves every other row as it was."""
    pool = M.init_cache(QWEN3_NEXT, 4, MAX_SEQ, dtype=jnp.float32)
    rng = np.random.default_rng(3)
    pool = jax.tree.map(lambda a: jnp.asarray(
        rng.integers(1, 9, a.shape) if a.dtype == jnp.int32
        else rng.standard_normal(a.shape), a.dtype), pool)
    gdn = [c["gdn"] for c in pool["slots"] if "gdn" in c]
    assert gdn and all({"conv", "state"} <= set(g) for g in gdn)
    blanked = M.blank_cache_row(pool, 2)
    for a, b in zip(jax.tree.leaves(pool), jax.tree.leaves(blanked)):
        a, b = np.asarray(a), np.asarray(b)
        assert (b[:, 2] == 0).all()
        np.testing.assert_array_equal(np.delete(b, 2, axis=1),
                                      np.delete(a, 2, axis=1))


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_post_forward_exception_runs_the_forward_once(models, baselines,
                                                      name):
    """A sample-stage exception after the donated forward: the retry
    samples from the same logits, so ``jit_decode_step`` runs once a tick
    and tokens and pool equal the fault-free run's."""
    plan = R.FaultPlan.single("sample", "exception", step=2)
    sched = _sched(models, name, fault_plan=plan)
    forwards = []
    step = sched._decode_inplace
    sched._decode_inplace = lambda *a, **k: forwards.append(1) or step(
        *a, **k)
    out = sched.run()
    assert plan.triggered and not sched.failed
    assert sched.health.counters["retry"] == 1
    assert len(forwards) == sum(s.phase == "decode" for s in sched.stats)
    base_sched, base = baselines(name)
    assert sorted(out) == sorted(base)
    for uid, toks in base.items():
        np.testing.assert_array_equal(out[uid], toks)
    _assert_pools_equal(sched.cache, base_sched.cache)


@pytest.mark.parametrize("name", ["dense", "qwen3-next"])
def test_decode_retry_exhaustion_leaves_the_advanced_pool(models, name):
    """A tick out of retries raises; ``.cache`` is the pool its one forward
    advanced, the pool a fault-free run holds after the same tick."""
    plan = R.FaultPlan.single("sample", "exception", step=1, times=99)
    sched = _sched(models, name, fault_plan=plan,
                   retry=R.RetryPolicy(max_retries=1))
    with pytest.raises(RuntimeError, match="failed after 1 retries"):
        sched.run()
    assert sched.step_idx == 1
    clean = _sched(models, name)
    clean.step()
    clean.step()
    _assert_pools_equal(sched.cache, clean.cache)
