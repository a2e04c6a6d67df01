"""Serving resilience (PR 10): deterministic fault injection, per-request
isolation, retry/shed/deadline policy, and the graceful-degradation ladder.

The contract under test (see "Resilience contract" in ``tests/README.md``):

* **Survivor bit-identity.**  With any single injected fault (any stage x
  any kind), every surviving request's generated tokens are bit-identical
  to the same trace run fault-free -- on the fused, donated decode tick,
  with gather dispatch and with the bcsr SpMM traced inside it.  Poison
  stays in its batch row (per-row independence of attention, prefix-stable
  MoE, bcsr dispatch, and per-request sampling keys), and host-side
  failures retry from untouched state (faults fire before any key split;
  a decode retry samples again from the logits its one forward made).
* **Zero new host syncs.**  The scheduler samples on the device and the
  health bits ride the per-step token fetch: exactly one
  ``jax.device_get`` per decode step.
* **Policy.**  Bounded exponential-backoff retries, TTFT/total deadlines
  on a fake clock, a bounded admission queue with reject / drop-oldest
  shed policies, and the kv_wide -> mask_ref ladder.
* **Refusals.**  A fault stage the serving path never calls, and a
  pipeline depth other than 0, are errors at construction.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision
from repro.core.masks import AttnMaskSpec
from repro.launch.serve import ServeLoop, ServeScheduler, _percentiles_ms
from repro.models import model as M
from repro.models import moe
from repro.models.config import ArchConfig
from repro.runtime import resilience as R

from test_serve_inplace import reuse_programs

TINY = ArchConfig(
    name="tiny-resilience", family="moe", d_model=32, n_heads=2,
    n_kv_heads=1, d_ff=48, vocab_size=64, block_unit=("attn", "attn+moe"),
    n_repeats=2, head_dim=16, n_experts=4, top_k=1, capacity_factor=1.0,
    moe_shared_expert=True, policy="f32")

PROMPT, GEN, MAX_SEQ = 8, 5, 16
N_REQ, SLOTS = 3, 2


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, TINY.vocab_size, PROMPT) for _ in range(N_REQ)]


def _sched(params, *, dispatch="bcsr", plan=None, kv_quant=None,
           temperature=0.0, **kw):
    return reuse_programs(ServeScheduler(
        params, TINY, max_seq=MAX_SEQ, max_slots=SLOTS, dispatch=dispatch,
        temperature=temperature, cache_dtype=jnp.float32, kv_quant=kv_quant,
        fault_plan=plan, **kw))


def _run_sched(params, prompts, **kw):
    sched = _sched(params, **kw)
    for p in prompts:
        sched.submit(p, GEN)
    return sched, sched.run()


@pytest.fixture(scope="module")
def baselines(params, prompts):
    """Fault-free token maps per (dispatch, kv_quant) combo, computed
    lazily so only combos a test actually compares against are run;
    ``pool(key)`` is the slot pool such a run leaves behind."""
    cache = {}

    class Lazy:
        def _run(self, key):
            if key not in cache:
                dispatch, kvq = key
                cache[key] = _run_sched(params, prompts, dispatch=dispatch,
                                        kv_quant=kvq)
            return cache[key]

        def __getitem__(self, key):
            return self._run(key)[1]

        def pool(self, key):
            return self._run(key)[0].cache

    return Lazy()


def _assert_survivors_identical(out, base, *, failed_uids=()):
    for uid, toks in base.items():
        if uid in failed_uids:
            continue
        assert uid in out, f"survivor {uid} missing from faulted run"
        np.testing.assert_array_equal(
            out[uid], toks,
            err_msg=f"survivor {uid} tokens diverged under fault")


def _assert_pools_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------- fault registry --

class TestFaultPlan:
    def test_spec_validation(self):
        with pytest.raises(ValueError, match="stage"):
            R.FaultSpec(stage="nope", kind="nan")
        with pytest.raises(ValueError, match="kind"):
            R.FaultSpec(stage="sample", kind="nope")
        with pytest.raises(ValueError, match="quantize"):
            R.FaultSpec(stage="quantize", kind="exception")

    @pytest.mark.parametrize("stage", ["attention", "route", "execute"])
    def test_stage_the_serving_path_never_calls_is_refused(self, stage):
        """A spec that could never fire is an error, not a silent no-op."""
        with pytest.raises(ValueError, match="stage must be one of"):
            R.FaultSpec(stage=stage, kind="nan")

    def test_poison_rows(self):
        x = jnp.ones((4, 3))
        y = np.asarray(R.poison_rows(x, [1, 3], "nan"))
        assert np.isnan(y[[1, 3]]).all() and (y[[0, 2]] == 1.0).all()
        z = np.asarray(R.poison_rows(x, [0], "inf"))
        assert np.isinf(z[0]).all() and (z[1:] == 1.0).all()
        assert R.poison_rows(x, [], "nan") is x

    def test_times_and_reset(self):
        plan = R.FaultPlan.single("sample", "nan", times=2)
        x = jnp.ones((2, 4))
        for _ in range(3):
            plan.apply("sample", x, step=0)
        assert len(plan.triggered) == 2
        plan.reset()
        assert plan.triggered == [] and len(plan._armed(
            "sample", step=None)) == 1

    def test_selectors(self):
        plan = R.FaultPlan.single("sample", "nan", uid=7, step=3)
        x = jnp.ones((2, 4))
        # wrong step: no fire
        assert plan.apply("sample", x, step=2, uids=[7, None]) is x
        # right step, uid not resident: no fire, stays armed
        assert plan.apply("sample", x, step=3, uids=[1, 2]) is x
        y = plan.apply("sample", x, step=3, uids=[1, 7])
        assert np.isnan(np.asarray(y)[1]).all()
        assert plan.triggered == [("sample", "nan", 3, (1,))]

    def test_exception_and_straggler(self):
        plan = R.FaultPlan([R.FaultSpec("sample", "exception", step=1),
                            R.FaultSpec("sample", "straggler", step=2,
                                        delay_s=0.0)])
        x = jnp.ones((1, 2))
        plan.apply("sample", x, step=0)
        with pytest.raises(R.InjectedFault):
            plan.apply("sample", x, step=1)
        plan.apply("sample", x, step=2)   # sleeps 0s, logs
        kinds = [t[1] for t in plan.triggered]
        assert kinds == ["exception", "straggler"]

    def test_random_plan_seeded(self):
        uids = list(range(20))
        a = R.FaultPlan.random(5, uids, 0.4)
        b = R.FaultPlan.random(5, uids, 0.4)
        assert [dataclasses.astuple(s) for s in a.specs] == \
               [dataclasses.astuple(s) for s in b.specs]
        assert 0 < len(a.specs) < len(uids)


class TestPolicies:
    def test_retry_schedule(self):
        rp = R.RetryPolicy(max_retries=4, base_delay_s=0.1, multiplier=2.0,
                           max_delay_s=0.5)
        assert rp.schedule() == pytest.approx([0.1, 0.2, 0.4, 0.5])
        assert R.RetryPolicy(base_delay_s=0.0).schedule() == [0.0, 0.0]

    def test_ladder_order_and_threshold(self):
        lad = R.DegradationLadder(["mask_ref", "kv_wide"], fail_threshold=2)
        rungs = [lad.note_failure() for _ in range(5)]
        # canonical order regardless of construction order, every 2 failures
        assert rungs == [None, "kv_wide", None, "mask_ref", None]
        st = lad.state()
        assert st["applied"] == ["kv_wide", "mask_ref"]
        assert st["pending"] == [] and st["failures"] == 5
        with pytest.raises(ValueError, match="unknown ladder rungs"):
            R.DegradationLadder(["serial"])

    def test_ladder_for_serving_filters(self):
        lad = R.DegradationLadder.for_serving(kv_quant=None, attn_mask=None)
        assert lad.pending == []
        spec = AttnMaskSpec(local=True, impl="sparse")
        lad = R.DegradationLadder.for_serving(kv_quant="int8",
                                              attn_mask=spec)
        assert lad.pending == ["kv_wide", "mask_ref"]
        lad = R.DegradationLadder.for_serving(
            kv_quant="int8", attn_mask=dataclasses.replace(spec, impl="ref"))
        assert lad.pending == ["kv_wide"]

    def test_percentiles_empty_and_dirty(self):
        z = _percentiles_ms([])
        assert z == {"p50": 0.0, "p99": 0.0, "mean": 0.0, "n": 0}
        assert _percentiles_ms([None, float("nan"), float("inf")])["n"] == 0
        d = _percentiles_ms([0.001, None, 0.003, float("nan")])
        assert d["n"] == 2 and d["p50"] == pytest.approx(2.0)


# ----------------------------------------------------- satellite fixes ----

class TestQuantizeNonFinite:
    def test_raises_by_default(self):
        x = jnp.array([[1.0, jnp.nan], [2.0, 3.0]])
        with pytest.raises(FloatingPointError, match="quantize_rows"):
            precision.quantize_rows(x, "int8")
        with pytest.raises(FloatingPointError, match="quantize_blocks"):
            precision.quantize_blocks(x[None], "fp8_e4m3")
        with pytest.raises(FloatingPointError, match="quantize_tensor"):
            precision.quantize_tensor(jnp.array([jnp.inf, 1.0]), "int8")

    def test_saturate_clamps_deterministically(self):
        x = jnp.array([[jnp.nan, jnp.inf, -jnp.inf, 2.0]])
        q, s = precision.quantize_rows(x, "int8", saturate=True)
        assert np.isfinite(np.asarray(s)).all()
        deq = np.asarray(precision.dequantize_rows(q, s))
        assert np.isfinite(deq).all()       # 3e38 clamp leaves rounding room
        assert deq[0, 0] == 0.0             # NaN -> 0
        q2, s2 = precision.quantize_rows(x, "int8", saturate=True)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))

    def test_noop_under_jit(self):
        # traced values cannot be checked: the guard must not sync or raise
        # at trace time.  The resulting stream is silently corrupt (that is
        # exactly why serving carries a runtime health layer) -- all this
        # test pins down is that jit compilation and execution succeed.
        f = jax.jit(lambda v: precision.quantize_rows(v, "int8"))
        q, s = f(jnp.array([[1.0, jnp.nan]]))
        assert np.asarray(q).shape == (1, 2)
        assert np.asarray(s).shape == (1,)

    def test_finite_path_unchanged(self):
        x = jnp.linspace(-3, 3, 12).reshape(3, 4)
        a = precision.quantize_rows(x, "int8")
        b = precision.quantize_rows(x, "int8", saturate=True)
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_routed_stream_rejects_corrupt_slots():
    with pytest.raises(ValueError, match="flat_slot out of range"):
        moe._build_routed_stream(np.array([[-2, 0, 1]]), 4, 2, 2, 2, 2,
                                 np.float32)


def test_blank_cache_row_resets_quant_row():
    cache = M.init_cache(TINY, 4, MAX_SEQ, dtype=jnp.float32,
                         kv_quant="int8")
    poisoned = R.corrupt_quant_scales(cache, [2], "nan")
    leaves = jax.tree_util.tree_leaves_with_path(poisoned)
    assert any(np.isnan(np.asarray(a)[:, 2]).any() for p, a in leaves
               if "scale" in str(p))
    blanked = M.blank_cache_row(poisoned, 2)

    def check(path, a):
        a = np.asarray(a)
        want = 1.0 if "scale" in str(path) else 0.0
        np.testing.assert_array_equal(a[:, 2], np.full_like(a[:, 2], want))

    jax.tree_util.tree_map_with_path(check, blanked)


def test_dequantize_cache_round_trip():
    cache = M.init_cache(TINY, 2, MAX_SEQ, dtype=jnp.float32,
                         kv_quant="int8")
    wide = R.dequantize_cache(cache, jnp.float32)
    paths = [str(p) for p, _ in jax.tree_util.tree_leaves_with_path(wide)]
    assert not any("scale" in p for p in paths)
    # all-zero cache dequantizes to exact zeros (the scale-1.0 convention)
    for p, a in jax.tree_util.tree_leaves_with_path(wide):
        assert (np.asarray(a) == 0).all()


# ------------------------------------------------------------ fault matrix --

# (stage, kind, selector-kwargs, needs_kv_quant). uid 0 is resident from
# step 0; full stage x kind coverage runs with the bcsr SpMM traced inside
# the fused tick, cross-checks on both backends keep tier-1 runtime sane.
MATRIX = [
    ("prefill", "nan", dict(uid=1), None),
    ("prefill", "inf", dict(uid=0), None),
    ("prefill", "exception", dict(uid=1), None),
    ("sample", "nan", dict(uid=0, step=2), None),
    ("sample", "inf", dict(uid=1, step=0), None),
    ("quantize", "nan", dict(uid=0, step=1), "int8"),
    ("quantize", "inf", dict(uid=1, step=0), "int8"),
]


@pytest.mark.parametrize("stage,kind,sel,kvq",
                         MATRIX, ids=[f"{s}-{k}" for s, k, _, _ in MATRIX])
def test_fault_matrix_bcsr_depth1(params, prompts, baselines,
                                  stage, kind, sel, kvq):
    """Every stage x kind keeps survivors bit-identical, on the fused tick
    with bcsr dispatch (the configuration this matrix has always swept)."""
    plan = R.FaultPlan.single(stage, kind, **sel)
    sched, out = _run_sched(params, prompts, dispatch="bcsr", plan=plan,
                            kv_quant=kvq)
    assert plan.triggered, "fault never fired -- dead test"
    failed = {r.uid for r in sched.failed}
    if kind in ("exception", "straggler") or stage == "prefill":
        # host failures retry from untouched state; stragglers just stall:
        # nobody fails, every request finishes with baseline tokens
        assert not failed
    else:
        assert failed, "activation poison must fail its request"
    _assert_survivors_identical(out, baselines[("bcsr", kvq)],
                                failed_uids=failed)
    # the poisoned/retried paths surface in the health summary
    h = sched.summary()["health"]
    assert h["faults_triggered"] == plan.triggered


CROSS = [
    ("bcsr", "quantize", "nan", dict(uid=0, step=0), "int8"),
    ("bcsr", "sample", "nan", dict(uid=1, step=2), None),
    ("bcsr", "prefill", "nan", dict(uid=0), None),
    ("gather", "sample", "inf", dict(uid=0, step=1), None),
    ("gather", "quantize", "inf", dict(uid=1, step=1), "int8"),
    # the tick has advanced the donated pool when the sample hook fires:
    # its retry must sample again, not run the forward twice
    ("gather", "sample", "exception", dict(step=2), None),
    ("bcsr", "sample", "exception", dict(step=2), None),
]


@pytest.mark.parametrize(
    "dispatch,stage,kind,sel,kvq", CROSS,
    ids=[f"{d}-{s}-{k}" for d, s, k, _, _ in CROSS])
def test_fault_matrix_cross(params, prompts, baselines, dispatch, stage,
                            kind, sel, kvq):
    """Both dispatch backends hold the same isolation contract."""
    plan = R.FaultPlan.single(stage, kind, **sel)
    sched, out = _run_sched(params, prompts, dispatch=dispatch, plan=plan,
                            kv_quant=kvq)
    assert plan.triggered
    failed = {r.uid for r in sched.failed}
    if kind == "exception" or stage == "prefill":
        assert not failed
    else:
        assert failed
    _assert_survivors_identical(out, baselines[(dispatch, kvq)],
                                failed_uids=failed)
    if not failed:
        # the pool left behind is the fault-free run's: a retry that ran a
        # step's forward twice would advance its state (MoE occupancy
        # counts) twice, which the tokens alone may not show
        _assert_pools_equal(sched.cache, baselines.pool((dispatch, kvq)))


def test_loop_poison_isolated_per_row(params):
    """ServeLoop: a row poisoned at the sample stage is flagged in
    health_rows while the other row's tokens stay bit-identical (per-row
    independence)."""
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, PROMPT), 0,
                                 TINY.vocab_size)
    loop = ServeLoop(params, TINY, max_seq=MAX_SEQ, dispatch="bcsr")
    base = loop.run(prompts, GEN)
    assert loop.health_rows.all()
    plan = R.FaultPlan.single("sample", "nan", row=1, step=2)
    fl = ServeLoop(params, TINY, max_seq=MAX_SEQ, dispatch="bcsr",
                   fault_plan=plan)
    out = fl.run(prompts, GEN)
    assert list(fl.health_rows) == [True, False]
    np.testing.assert_array_equal(out[0], base[0])
    assert fl.summary()["health"]["rows_finite"] == [True, False]


# --------------------------------------------------- retry / deadlines ----

class TestRetryPolicyIntegration:
    def test_prefill_retry_to_success(self, params, prompts, baselines):
        plan = R.FaultPlan.single("prefill", "nan", uid=0)
        sched, out = _run_sched(params, prompts, plan=plan)
        assert not sched.failed
        req0 = next(r for r in sched.finished if r.uid == 0)
        assert req0.retries == 1
        _assert_survivors_identical(out, baselines[("bcsr", None)])

    def test_prefill_retry_exhaustion(self, params, prompts, baselines):
        plan = R.FaultPlan.single("prefill", "nan", uid=0, times=99)
        retry = R.RetryPolicy(max_retries=2)
        sched, out = _run_sched(params, prompts, plan=plan, retry=retry)
        failed = {r.uid for r in sched.failed}
        assert failed == {0}
        req0 = sched.failed[0]
        assert req0.state == "failed" and req0.retries == 2
        assert req0.fail_reason == "prefill_poisoned"
        assert req0.slot is None         # slot freed for the next admit
        _assert_survivors_identical(out, baselines[("bcsr", None)],
                                    failed_uids=failed)

    def test_backoff_delays_follow_schedule(self, params, prompts):
        plan = R.FaultPlan.single("prefill", "nan", uid=0, times=99)
        retry = R.RetryPolicy(max_retries=3, base_delay_s=0.01,
                              multiplier=2.0, max_delay_s=0.03)
        sched = _sched(params, plan=plan, retry=retry)
        slept = []
        sched._sleep = slept.append
        for p in prompts:
            sched.submit(p, GEN)
        sched.run()
        assert slept == pytest.approx([0.01, 0.02, 0.03])

    def test_decode_retry_exhaustion_raises(self, params, prompts):
        """A decode step that exhausts its retries raises and leaves the
        pool its one forward advanced in ``.cache``: the pool a fault-free
        run holds after the same tick."""
        plan = R.FaultPlan.single("sample", "exception", step=1, times=99)
        sched = _sched(params, plan=plan, retry=R.RetryPolicy(max_retries=1))
        for p in prompts:
            sched.submit(p, GEN)
        with pytest.raises(RuntimeError, match="failed after 1 retries"):
            sched.run()
        assert sched.step_idx == 1 and sched._advanced is None
        clean = _sched(params)
        for p in prompts:
            clean.submit(p, GEN)
        clean.step()
        clean.step()
        _assert_pools_equal(sched.cache, clean.cache)

    @pytest.mark.parametrize("stage", ["prefill", "decode"])
    def test_program_error_propagates_unretried(self, params, prompts, stage):
        """Only ``R.RETRYABLE`` is retried: any other exception is a fault
        of the program and leaves ``run`` as itself, no request FAILED."""
        sched = _sched(params)

        def bug(*a, **k):
            raise ZeroDivisionError("a bug, not a flaky request")
        setattr(sched, f"_{stage}_attempt", bug)
        for p in prompts:
            sched.submit(p, GEN)
        with pytest.raises(ZeroDivisionError):
            sched.run()
        assert not sched.failed
        assert not sched.health.counters     # no retry, no recorded error


class TestDeadlinesAndShedding:
    def _sched(self, params, **kw):
        return reuse_programs(ServeScheduler(
            params, TINY, max_seq=MAX_SEQ, max_slots=1, dispatch="gather",
            cache_dtype=jnp.float32, **kw))

    def test_deadlines_fake_clock(self, params, prompts):
        t = [0.0]
        sched = self._sched(params, clock=lambda: t[0])
        sched.submit(prompts[0], GEN)
        r1 = sched.submit(prompts[1], GEN, ttft_deadline_s=0.5)
        r2 = sched.submit(prompts[2], GEN, deadline_s=0.3)
        t[0] = 1.0
        sched.step()
        assert {r.uid for r in sched.shed} == {r1.uid, r2.uid}
        assert r1.fail_reason == "ttft_deadline"
        assert r2.fail_reason == "deadline"
        sched.run()
        assert len(sched.finished) == 1
        s = sched.summary()
        assert s["requests"]["shed"] == 2
        assert {e["reason"] for e in s["health"]["shed"]} == \
               {"ttft_deadline", "deadline"}

    def test_resident_total_deadline_fails(self, params, prompts):
        t = [0.0]
        sched = self._sched(params, clock=lambda: t[0])
        req = sched.submit(prompts[0], MAX_SEQ - PROMPT, deadline_s=0.5)
        sched.step()                     # admitted, decoding
        assert req.state == "active"
        t[0] = 1.0
        sched.step()
        assert req.state == "failed" and req.fail_reason == "deadline"
        assert not sched.has_work()

    def test_bounded_queue_reject(self, params, prompts):
        sched = self._sched(params, max_queue=1, shed_policy="reject")
        sched.submit(prompts[0], 2)
        with pytest.raises(R.ShedError, match="queue full"):
            sched.submit(prompts[1], 2)
        assert sched.health.counters["shed"] == 1

    def test_bounded_queue_drop_oldest(self, params, prompts):
        sched = self._sched(params, max_queue=1, shed_policy="drop_oldest")
        a = sched.submit(prompts[0], 2)
        b = sched.submit(prompts[1], 2)
        assert a.state == "shed" and a.fail_reason == "queue_full_drop_oldest"
        assert list(sched.queue) == [b]

    def test_empty_run_summary_zeroes(self, params, prompts):
        # every request shed before first token: percentiles must be zeros
        t = [0.0]
        sched = self._sched(params, clock=lambda: t[0])
        sched.submit(prompts[0], GEN, deadline_s=0.1)
        t[0] = 1.0
        sched.step()
        s = sched.summary()
        assert s["token_latency_ms"]["n"] == 0
        assert s["first_token_ms"] == {"p50": 0.0, "p99": 0.0, "mean": 0.0,
                                       "n": 0}


# ------------------------------------------------------------- ladder -----

def test_ladder_integration_walks_rungs(params, prompts):
    """fail_threshold=1: each failure applies the next applicable rung --
    kv_wide flips the live cache to scale-free wide f32, mask_ref sends
    masked prefill attention to the reference path -- and the scheduler
    keeps serving afterwards."""
    plan = R.FaultPlan([
        R.FaultSpec("sample", "nan", uid=0, step=0),
        R.FaultSpec("sample", "nan", uid=1, step=1),
    ])
    spec = AttnMaskSpec(local=True, impl="sparse")
    sched, out = _run_sched(params, prompts, kv_quant="int8", plan=plan,
                            attn_mask=spec, fail_threshold=1)
    st = sched.ladder.state()
    assert st["applied"] == ["kv_wide", "mask_ref"]
    assert sched.kv_quant is None and sched.attn_mask.impl == "ref"
    paths = [str(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(sched.cache)]
    assert not any("scale" in p for p in paths)
    assert len(sched.finished) == 1      # the non-faulted request completed
    degr = [e for e in sched.summary()["health"]["events"]
            if e["event"] == "degrade"]
    assert [e["rung"] for e in degr] == ["kv_wide", "mask_ref"]


def test_kv_wide_rung_after_a_fused_forward(params, prompts, baselines):
    """A sample-stage exception fires after the donated forward: the
    kv_wide rung it triggers rebuilds the pool that forward advanced (the
    one it was given is gone), and the retry samples the step's int8-KV
    logits, so tokens through that step match the int8 baseline and every
    request finishes."""
    plan = R.FaultPlan.single("sample", "exception", step=2)
    sched, out = _run_sched(params, prompts, dispatch="gather",
                            kv_quant="int8", plan=plan, fail_threshold=1)
    assert sched.ladder.state()["applied"] == ["kv_wide"]
    assert not sched.failed and len(sched.finished) == N_REQ
    paths = [str(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(sched.cache)]
    assert not any("scale" in p for p in paths)
    base = baselines[("gather", "int8")]
    for uid in (0, 1):       # resident from tick 0: prefill + ticks 0..2
        np.testing.assert_array_equal(out[uid][:4], base[uid][:4])


def test_mask_ref_rung_rewrites_spec(params):
    spec = AttnMaskSpec(local=True, impl="sparse")
    loop = ServeLoop(params, TINY, max_seq=MAX_SEQ, dispatch="gather",
                     attn_mask=spec)
    assert "mask_ref" in loop.ladder.pending
    loop._apply_rung("mask_ref")
    assert loop.attn_mask.impl == "ref"
    assert loop.attn_mask.local == spec.local   # only impl changes


def test_pipeline_depth_other_than_zero_is_refused(params):
    with pytest.raises(ValueError, match="pipeline_depth must be 0"):
        _sched(params, pipeline_depth=1)


# ------------------------------------------------------- sync accounting --

def test_depth0_samples_on_device_with_one_fetch(params, prompts, baselines,
                                                monkeypatch):
    """The fused gather tick samples on the device: one
    ``jax.device_get`` per decode step, and no eager argmax beyond each
    admission's first token."""
    sched = _sched(params, dispatch="gather")
    for p in prompts:
        sched.submit(p, GEN)
    fetches, eager_argmax = [], []
    orig_get, orig_argmax = jax.device_get, jnp.argmax

    def argmax(x, *a, **k):
        if not isinstance(x, jax.core.Tracer):
            eager_argmax.append(1)
        return orig_argmax(x, *a, **k)
    monkeypatch.setattr(jax, "device_get", lambda x: fetches.append(1)
                        or orig_get(x))
    monkeypatch.setattr(jnp, "argmax", argmax)
    out = sched.run()
    decode_steps = sum(1 for s in sched.stats if s.phase == "decode")
    prefills = sum(1 for s in sched.stats if s.phase == "prefill")
    assert decode_steps and prefills == len(prompts)
    assert len(fetches) == decode_steps
    assert len(eager_argmax) == prefills
    _assert_survivors_identical(out, baselines[("gather", None)])


# ------------------------------------------------------------- stress -----

@pytest.mark.stress
def test_randomized_fault_stress(params):
    """Seeded random trace x random fault plan: staggered joins, random
    faults across stages/kinds, and every survivor still bit-identical to
    the fault-free run of the same trace."""
    rng = np.random.default_rng(7)
    n_req = 10
    prompts = [rng.integers(0, TINY.vocab_size, int(rng.integers(4, PROMPT)))
               for _ in range(n_req)]
    gens = [int(rng.integers(2, GEN + 1)) for _ in range(n_req)]

    def drive(plan):
        sched = ServeScheduler(
            params, TINY, max_seq=MAX_SEQ, max_slots=4, dispatch="bcsr",
            cache_dtype=jnp.float32, fault_plan=plan)
        pending = list(zip(prompts, gens))
        i = 0
        while pending or sched.has_work():
            # staggered arrivals: up to 2 submissions per tick
            for _ in range(min(2, len(pending))):
                p, g = pending.pop(0)
                sched.submit(p, g)
            if sched.has_work():
                sched.step()
            i += 1
            assert i < 500, "scheduler wedged"
        return sched, {r.uid: np.asarray(r.tokens, np.int32)
                       for r in sched.finished}

    _, base = drive(None)
    assert len(base) == n_req
    plan = R.FaultPlan.random(11, list(range(n_req)), 0.5)
    assert plan.specs, "seed produced no faults -- pick another"
    sched, out = drive(plan)
    failed = {r.uid for r in sched.failed}
    assert plan.triggered
    _assert_survivors_identical(out, base, failed_uids=failed)
    # terminal states partition the request set
    assert failed | set(out) == set(range(n_req))
