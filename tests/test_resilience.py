"""Serving resilience (PR 10): deterministic fault injection, per-request
isolation, retry/shed/deadline policy, and the graceful-degradation ladder.

The contract under test (see "Resilience contract" in ``tests/README.md``):

* **Survivor bit-identity.**  With any single injected fault (any stage x
  any kind), every surviving request's generated tokens are bit-identical
  to the same trace run fault-free -- on both dispatch backends, at
  pipeline depth 0 and 1.  Poison stays in its batch row (per-row
  independence of attention, prefix-stable MoE, bcsr dispatch, and
  per-request sampling keys), and host-side failures retry from untouched
  state (faults fire before any key split or cache commit).
* **Zero new host syncs.**  At both depths the scheduler samples on the
  device and the health bits ride the per-step token fetch: exactly one
  ``jax.device_get`` per decode step.
* **Policy.**  Bounded exponential-backoff retries, TTFT/total deadlines
  on a fake clock, a bounded admission queue with reject / drop-oldest
  shed policies, and the kv_wide -> mask_ref -> pipeline_serial ladder.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision
from repro.core.masks import AttnMaskSpec
from repro.kernels import engine
from repro.launch import serve
from repro.launch.serve import ServeLoop, ServeScheduler, _percentiles_ms
from repro.models import model as M
from repro.models import moe
from repro.models.config import ArchConfig
from repro.runtime import resilience as R

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


def _run_sched(params, prompts, *, dispatch="bcsr", depth=1, plan=None,
               kv_quant=None, temperature=0.0, **kw):
    sched = ServeScheduler(
        params, TINY, max_seq=MAX_SEQ, max_slots=SLOTS, dispatch=dispatch,
        two_phase=dispatch == "bcsr", temperature=temperature,
        cache_dtype=jnp.float32, pipeline_depth=depth, kv_quant=kv_quant,
        fault_plan=plan, **kw)
    for p in prompts:
        sched.submit(p, GEN)
    return sched, sched.run()


@pytest.fixture(scope="module")
def baselines(params, prompts):
    """Fault-free token maps per (dispatch, depth, kv_quant) combo, computed
    lazily so only combos a test actually compares against are run;
    ``pool(key)`` is the slot pool such a run leaves behind."""
    cache = {}

    class Lazy:
        def _run(self, key):
            if key not in cache:
                dispatch, depth, kvq = key
                cache[key] = _run_sched(params, prompts, dispatch=dispatch,
                                        depth=depth, kv_quant=kvq)
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


# --------------------------------------------------------- fault registry --

class TestFaultPlan:
    def test_spec_validation(self):
        with pytest.raises(ValueError, match="stage"):
            R.FaultSpec(stage="nope", kind="nan")
        with pytest.raises(ValueError, match="kind"):
            R.FaultSpec(stage="sample", kind="nope")
        with pytest.raises(ValueError, match="quantize"):
            R.FaultSpec(stage="quantize", kind="exception")

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
            "sample", step=None, layer=0)) == 1

    def test_selectors(self):
        plan = R.FaultPlan.single("execute", "nan", uid=7, step=3)
        x = jnp.ones((2, 4))
        # wrong step: no fire
        assert plan.apply("execute", x, step=2, uids=[7, None]) is x
        # right step, uid not resident: no fire, stays armed
        assert plan.apply("execute", x, step=3, uids=[1, 2]) is x
        y = plan.apply("execute", x, step=3, uids=[1, 7])
        assert np.isnan(np.asarray(y)[1]).all()
        assert plan.triggered == [("execute", "nan", 3, (1,))]

    def test_exception_and_straggler(self):
        plan = R.FaultPlan([R.FaultSpec("route", "exception", step=1),
                            R.FaultSpec("route", "straggler", step=2,
                                        delay_s=0.0)])
        x = jnp.ones((1, 2))
        plan.apply("route", x, step=0)
        with pytest.raises(R.InjectedFault):
            plan.apply("route", x, step=1)
        plan.apply("route", x, step=2)   # sleeps 0s, logs
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
        lad = R.DegradationLadder(["pipeline_serial", "kv_wide", "mask_ref"],
                                  fail_threshold=2)
        rungs = [lad.note_failure() for _ in range(7)]
        # canonical order regardless of construction order, every 2 failures
        assert rungs == [None, "kv_wide", None, "mask_ref", None,
                         "pipeline_serial", None]
        st = lad.state()
        assert st["applied"] == ["kv_wide", "mask_ref", "pipeline_serial"]
        assert st["pending"] == [] and st["failures"] == 7

    def test_ladder_for_serving_filters(self):
        lad = R.DegradationLadder.for_serving(
            kv_quant=None, attn_mask=None, pipeline_depth=0)
        assert lad.pending == []
        spec = AttnMaskSpec(local=True, impl="sparse")
        lad = R.DegradationLadder.for_serving(
            kv_quant="int8", attn_mask=spec, pipeline_depth=1)
        assert lad.pending == ["kv_wide", "mask_ref", "pipeline_serial"]
        lad = R.DegradationLadder.for_serving(
            kv_quant=None, attn_mask=dataclasses.replace(spec, impl="ref"),
            pipeline_depth=1)
        assert lad.pending == ["pipeline_serial"]

    def test_percentiles_empty_and_dirty(self):
        z = _percentiles_ms([])
        assert z == {"p50": 0.0, "p99": 0.0, "mean": 0.0, "n": 0}
        assert _percentiles_ms([None, float("nan"), float("inf")])["n"] == 0
        d = _percentiles_ms([0.001, None, 0.003, float("nan")])
        assert d["n"] == 2 and d["p50"] == pytest.approx(2.0)


# ----------------------------------------------------- satellite fixes ----

class TestStreamPipelineAbort:
    def test_failing_wait_releases_all_slots(self, monkeypatch):
        pipe = engine.StreamPipeline(1)
        orig, calls = jax.block_until_ready, []

        def boom(h):
            calls.append(h)
            if len(calls) == 1:
                raise RuntimeError("deferred device error")
            return orig(h)

        pipe.push("a", jnp.zeros(3))
        monkeypatch.setattr(engine.jax, "block_until_ready", boom)
        with pytest.raises(RuntimeError, match="deferred device error"):
            pipe.push("b", jnp.zeros(3))   # waits "a" out -> raises
        assert len(pipe) == 0              # nothing leaked, nothing wedged
        monkeypatch.setattr(engine.jax, "block_until_ready", orig)
        pipe.push("c", jnp.zeros(3))       # still usable
        pipe.drain()
        assert len(pipe) == 0

    def test_failing_drain_empties(self, monkeypatch):
        pipe = engine.StreamPipeline(1)
        pipe.push("a", jnp.zeros(2))
        monkeypatch.setattr(
            engine.jax, "block_until_ready",
            lambda h: (_ for _ in ()).throw(RuntimeError("boom")))
        with pytest.raises(RuntimeError):
            pipe.drain()
        assert len(pipe) == 0


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
# step 0; full stage x kind coverage runs on the bcsr/depth-1 flagship,
# cross-checks on the other backend/depth combos keep tier-1 runtime sane.
MATRIX = [
    ("prefill", "nan", dict(uid=1), None),
    ("prefill", "inf", dict(uid=0), None),
    ("prefill", "exception", dict(uid=1), None),
    ("attention", "inf", dict(uid=0, step=1), None),
    ("route", "nan", dict(uid=0, step=1), None),
    ("route", "exception", dict(step=2), None),
    ("route", "straggler", dict(step=1, delay_s=0.0), None),
    ("execute", "nan", dict(uid=1, step=1), None),
    ("execute", "exception", dict(step=0), None),
    ("sample", "nan", dict(uid=0, step=2), None),
    ("sample", "inf", dict(uid=1, step=0), None),
    ("quantize", "nan", dict(uid=0, step=1), "int8"),
    ("quantize", "inf", dict(uid=1, step=0), "int8"),
]


@pytest.mark.parametrize("stage,kind,sel,kvq",
                         MATRIX, ids=[f"{s}-{k}" for s, k, _, _ in MATRIX])
def test_fault_matrix_bcsr_depth1(params, prompts, baselines,
                                  stage, kind, sel, kvq):
    """Flagship combo: every stage x kind keeps survivors bit-identical."""
    plan = R.FaultPlan.single(stage, kind, **sel)
    sched, out = _run_sched(params, prompts, dispatch="bcsr", depth=1,
                            plan=plan, kv_quant=kvq)
    assert plan.triggered, "fault never fired -- dead test"
    failed = {r.uid for r in sched.failed}
    if kind in ("exception", "straggler") or stage == "prefill":
        # host failures retry from untouched state; stragglers just stall:
        # nobody fails, every request finishes with baseline tokens
        assert not failed
    else:
        assert failed, "activation poison must fail its request"
    _assert_survivors_identical(out, baselines[("bcsr", 1, kvq)],
                                failed_uids=failed)
    # the poisoned/retried paths surface in the health summary
    h = sched.summary()["health"]
    assert h["faults_triggered"] == plan.triggered


CROSS = [
    ("bcsr", 0, "execute", "inf", dict(uid=0, step=1), None),
    ("bcsr", 0, "route", "exception", dict(step=1), None),
    ("bcsr", 0, "quantize", "nan", dict(uid=0, step=0), "int8"),
    ("gather", 1, "sample", "nan", dict(uid=1, step=2), None),
    ("gather", 1, "prefill", "nan", dict(uid=0), None),
    ("gather", 0, "sample", "inf", dict(uid=0, step=1), None),
    ("gather", 0, "quantize", "inf", dict(uid=1, step=1), "int8"),
    # the fused tick has advanced the donated pool when the sample hook
    # fires: its retry must sample again, not run the forward twice
    ("gather", 0, "sample", "exception", dict(step=2), None),
    ("gather", 1, "sample", "exception", dict(step=2), None),
]


@pytest.mark.parametrize(
    "dispatch,depth,stage,kind,sel,kvq", CROSS,
    ids=[f"{d}-d{p}-{s}-{k}" for d, p, s, k, _, _ in CROSS])
def test_fault_matrix_cross(params, prompts, baselines, dispatch, depth,
                            stage, kind, sel, kvq):
    """The other backend/depth combos hold the same isolation contract."""
    plan = R.FaultPlan.single(stage, kind, **sel)
    sched, out = _run_sched(params, prompts, dispatch=dispatch, depth=depth,
                            plan=plan, kv_quant=kvq)
    assert plan.triggered
    failed = {r.uid for r in sched.failed}
    if kind == "exception" or stage == "prefill":
        assert not failed
    else:
        assert failed
    _assert_survivors_identical(out, baselines[(dispatch, depth, kvq)],
                                failed_uids=failed)
    if not failed:
        # the pool left behind is the fault-free run's: a retry that ran a
        # step's forward twice would advance its state (MoE occupancy
        # counts) twice, which the tokens alone may not show
        for a, b in zip(jax.tree.leaves(sched.cache),
                        jax.tree.leaves(baselines.pool((dispatch, depth,
                                                        kvq)))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loop_poison_isolated_per_row(params):
    """ServeLoop: a poisoned batch row is flagged in health_rows while the
    other row's tokens stay bit-identical (per-row independence)."""
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, PROMPT), 0,
                                 TINY.vocab_size)
    loop = ServeLoop(params, TINY, max_seq=MAX_SEQ, dispatch="bcsr",
                     two_phase=True, pipeline_depth=1)
    base = loop.run(prompts, GEN)
    assert loop.health_rows.all()
    plan = R.FaultPlan.single("execute", "nan", row=1, step=2)
    fl = ServeLoop(params, TINY, max_seq=MAX_SEQ, dispatch="bcsr",
                   two_phase=True, pipeline_depth=1, fault_plan=plan)
    out = fl.run(prompts, GEN)
    assert list(fl.health_rows) == [True, False]
    np.testing.assert_array_equal(out[0], base[0])
    assert fl.summary()["health"]["rows_finite"] == [True, False]


def test_loop_exception_aborts_pipeline_and_stays_usable(params):
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, PROMPT), 0,
                                 TINY.vocab_size)
    base = ServeLoop(params, TINY, max_seq=MAX_SEQ, dispatch="bcsr",
                     two_phase=True, pipeline_depth=1).run(prompts, GEN)
    plan = R.FaultPlan.single("route", "exception", step=1)
    loop = ServeLoop(params, TINY, max_seq=MAX_SEQ, dispatch="bcsr",
                     two_phase=True, pipeline_depth=1, fault_plan=plan)
    with pytest.raises(R.InjectedFault):
        loop.run(prompts, GEN)
    assert len(loop._pipe) == 0      # no leaked in-flight execute
    out = loop.run(prompts, GEN)     # plan spent: clean rerun, same loop
    np.testing.assert_array_equal(out, base)


# --------------------------------------------------- retry / deadlines ----

class TestRetryPolicyIntegration:
    def test_prefill_retry_to_success(self, params, prompts, baselines):
        plan = R.FaultPlan.single("prefill", "nan", uid=0)
        sched, out = _run_sched(params, prompts, plan=plan)
        assert not sched.failed
        req0 = next(r for r in sched.finished if r.uid == 0)
        assert req0.retries == 1
        _assert_survivors_identical(out, baselines[("bcsr", 1, None)])

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
        _assert_survivors_identical(out, baselines[("bcsr", 1, None)],
                                    failed_uids=failed)

    def test_backoff_delays_follow_schedule(self, params, prompts):
        plan = R.FaultPlan.single("prefill", "nan", uid=0, times=99)
        retry = R.RetryPolicy(max_retries=3, base_delay_s=0.01,
                              multiplier=2.0, max_delay_s=0.03)
        sched = ServeScheduler(
            params, TINY, max_seq=MAX_SEQ, max_slots=SLOTS, dispatch="bcsr",
            two_phase=True, cache_dtype=jnp.float32, pipeline_depth=1,
            fault_plan=plan, retry=retry)
        slept = []
        sched._sleep = slept.append
        for p in prompts:
            sched.submit(p, GEN)
        sched.run()
        assert slept == pytest.approx([0.01, 0.02, 0.03])

    def test_decode_retry_exhaustion_raises(self, params, prompts):
        plan = R.FaultPlan.single("route", "exception", step=1, times=99)
        retry = R.RetryPolicy(max_retries=1)
        sched = ServeScheduler(
            params, TINY, max_seq=MAX_SEQ, max_slots=SLOTS, dispatch="bcsr",
            two_phase=True, cache_dtype=jnp.float32, pipeline_depth=1,
            fault_plan=plan, retry=retry)
        for p in prompts:
            sched.submit(p, GEN)
        with pytest.raises(RuntimeError, match="failed after 1 retries"):
            sched.run()
        assert len(sched._pipe) == 0     # aborted clean, not wedged

    @pytest.mark.parametrize("stage", ["prefill", "decode"])
    def test_program_error_propagates_unretried(self, params, prompts, stage):
        """Only ``R.RETRYABLE`` is retried: any other exception is a fault
        of the program and leaves ``run`` as itself, no request FAILED."""
        sched = ServeScheduler(
            params, TINY, max_seq=MAX_SEQ, max_slots=SLOTS, dispatch="bcsr",
            two_phase=True, cache_dtype=jnp.float32, pipeline_depth=1)

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
        return ServeScheduler(params, TINY, max_seq=MAX_SEQ, max_slots=1,
                              dispatch="gather", two_phase=False,
                              cache_dtype=jnp.float32, **kw)

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

def test_ladder_integration_walks_rungs(params, prompts, baselines):
    """fail_threshold=1: each failure applies the next applicable rung --
    kv_wide flips the live cache to scale-free wide f32, pipeline_serial
    drops to depth 0 -- and the scheduler keeps serving afterwards."""
    plan = R.FaultPlan([
        R.FaultSpec("execute", "nan", uid=0, step=0),
        R.FaultSpec("execute", "nan", uid=1, step=1),
    ])
    sched, out = _run_sched(params, prompts, depth=1, kv_quant="int8",
                            plan=plan, fail_threshold=1)
    st = sched.ladder.state()
    assert st["applied"] == ["kv_wide", "pipeline_serial"]
    assert sched.kv_quant is None and sched.pipeline_depth == 0
    assert sched._pipe.depth == 0
    paths = [str(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(sched.cache)]
    assert not any("scale" in p for p in paths)
    assert len(sched.finished) == 1      # the non-faulted request completed
    degr = [e for e in sched.summary()["health"]["events"]
            if e["event"] == "degrade"]
    assert [e["rung"] for e in degr] == ["kv_wide", "pipeline_serial"]


def test_kv_wide_rung_after_a_fused_forward(params, prompts, baselines):
    """A sample-stage exception on the fused path fires after the donated
    forward: the kv_wide rung it triggers rebuilds the pool that forward
    advanced (the one it was given is gone), and the retry samples the
    step's int8-KV logits, so tokens through that step match the int8
    baseline and every request finishes."""
    plan = R.FaultPlan.single("sample", "exception", step=2)
    sched, out = _run_sched(params, prompts, dispatch="gather", depth=0,
                            kv_quant="int8", plan=plan, fail_threshold=1)
    assert sched.ladder.state()["applied"] == ["kv_wide"]
    assert not sched.failed and len(sched.finished) == N_REQ
    paths = [str(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(sched.cache)]
    assert not any("scale" in p for p in paths)
    base = baselines[("gather", 0, "int8")]
    for uid in (0, 1):       # resident from tick 0: prefill + ticks 0..2
        np.testing.assert_array_equal(out[uid][:4], base[uid][:4])


def test_mask_ref_rung_rewrites_spec(params):
    spec = AttnMaskSpec(local=True, impl="sparse")
    loop = ServeLoop(params, TINY, max_seq=MAX_SEQ, dispatch="gather",
                     two_phase=False, attn_mask=spec)
    assert "mask_ref" in loop.ladder.pending
    loop._apply_rung("mask_ref")
    assert loop.attn_mask.impl == "ref"
    assert loop.attn_mask.local == spec.local   # only impl changes


# ------------------------------------------------------- sync accounting --

def test_depth1_health_adds_no_syncs(params, prompts, baselines,
                                     monkeypatch):
    """The healthy pipelined path performs exactly ONE device fetch per
    decode step (the token ids) -- the isfinite health bits ride inside
    it, not beside it."""
    sched = ServeScheduler(
        params, TINY, max_seq=MAX_SEQ, max_slots=SLOTS, dispatch="bcsr",
        two_phase=True, cache_dtype=jnp.float32, pipeline_depth=1)
    for p in prompts:
        sched.submit(p, GEN)
    fetches = []
    orig = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: fetches.append(1)
                        or orig(x))
    out = sched.run()
    decode_steps = sum(1 for s in sched.stats if s.phase == "decode")
    assert len(fetches) == decode_steps
    _assert_survivors_identical(out, baselines[("bcsr", 1, None)])


def test_depth0_samples_on_device_with_one_fetch(params, prompts, baselines,
                                                monkeypatch):
    """Depth 0 on the fused gather path samples each decode tick on the
    device too: one ``jax.device_get`` per decode step, and no eager
    argmax beyond each admission's first token."""
    sched = ServeScheduler(
        params, TINY, max_seq=MAX_SEQ, max_slots=SLOTS, dispatch="gather",
        two_phase=False, cache_dtype=jnp.float32, pipeline_depth=0)
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
    _assert_survivors_identical(out, baselines[("gather", 0, None)])


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
            two_phase=True, cache_dtype=jnp.float32, pipeline_depth=1,
            fault_plan=plan)
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
