"""ServeScheduler's spans and host-sync counter.

Every scheduler tick is one ``step`` record (a :class:`StepStat` and a
``serve.step`` profiler annotation) holding, in order, ``admit`` (with a
``prefill`` per admission), ``decode`` (the forward through the health
fetch, the device sampler inside it), ``writeback`` (the commit of the
slot pool that ``jit_decode_step`` took donated and updated in place:
only making the returned pool the scheduler's) and ``sample`` (the
per-row host bookkeeping).  The ``step`` record counts the host syncs made
inside it.  The tick-shape tests run on a dense stack, a top-1 MoE stack
and the hybrid Qwen3-Next stack.
Tier-1, tiny configs on the CPU.
"""
import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models import model as M
from repro.launch.serve import ServeLoop, ServeScheduler

from test_serve_inplace import MOE, QWEN3_NEXT, reuse_programs

TINY = ArchConfig(
    name="tiny-spans", family="dense", d_model=32, n_heads=2, n_kv_heads=1,
    d_ff=48, vocab_size=64, block_unit=("attn",), n_repeats=2, head_dim=16,
    policy="f32")
CONFIGS = {"dense": TINY, "moe": MOE, "qwen3-next": QWEN3_NEXT}
MAX_SEQ = 24
# per admission: the prefill's block, the poison gate's fetch, the position,
# the first token
SYNCS_PER_ADMISSION = 4


def _model(cfg):
    params = jax.jit(M.init_params, static_argnums=1)(jax.random.PRNGKey(0),
                                                      cfg)
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 9))),
             int(rng.integers(3, 7))) for _ in range(4)]
    return params, reqs


@pytest.fixture(scope="module")
def tiny():
    return _model(TINY)


@pytest.fixture(scope="module")
def served():
    """One scheduler run per config, made when a test first asks for it:
    ``served(name) -> (params, reqs, sched, tokens)``."""
    runs = {}

    def get(name):
        if name not in runs:
            params, reqs = _model(CONFIGS[name])
            runs[name] = (params, reqs,
                          *_serve(params, reqs, CONFIGS[name]))
        return runs[name]
    return get


def _serve(params, reqs, cfg=TINY, slots=2):
    sched = reuse_programs(ServeScheduler(
        params, cfg, max_seq=MAX_SEQ, max_slots=slots, dispatch="gather",
        pipeline_depth=0))
    for prompt, gen in reqs:
        sched.submit(prompt, gen)
    out = sched.run()
    return sched, out


def _ticks(stats):
    """{tick: (its step record, its other records in append order)}."""
    steps = {s.step: s for s in stats if s.phase == "step"}
    kids = {k: [s for s in stats if s.step == k and s.phase != "step"]
            for k in steps}
    return {k: (steps[k], kids[k]) for k in steps}


def _inside(inner, outer):
    return (outer.start <= inner.start
            and inner.start + inner.seconds <= outer.start + outer.seconds)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_each_tick_nests_its_phases_in_order(served, name):
    _, reqs, sched, _ = served(name)
    ticks = _ticks(sched.stats)
    assert sorted(ticks) == list(range(sched.step_idx))
    assert sum(s.phase == "step" for s in sched.stats) == sched.step_idx
    admitted = 0
    for k, (step, kids) in ticks.items():
        assert all(_inside(s, step) for s in kids), k
        phases = [s.phase for s in kids]
        # prefills close before the admit that holds them
        assert phases[-4:] == ["admit", "decode", "writeback", "sample"], (
            k, phases)
        assert set(phases[:-4]) <= {"prefill"}
        admit = kids[-4]
        assert all(_inside(s, admit) for s in kids[:-4])
        admitted += len(kids) - 4
        starts = [s.start for s in kids[-4:]]
        assert starts == sorted(starts)
        for a, b in zip(kids[-4:], kids[-3:]):
            assert a.start + a.seconds <= b.start      # one after another
    assert admitted == len(reqs)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_host_syncs_per_tick(served, name):
    _, _, sched, _ = served(name)
    for k, (step, kids) in _ticks(sched.stats).items():
        decode = next(s for s in kids if s.phase == "decode")
        # one fetch of the device-sampled ids and health bits
        assert decode.extra["active"] >= 1
        prefills = sum(s.phase == "prefill" for s in kids)
        assert step.extra["host_syncs"] == (
            1 + SYNCS_PER_ADMISSION * prefills), (k, kids)
    assert sched._host_syncs == sum(
        s.extra["host_syncs"] for s in sched.stats if s.phase == "step")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tokens_unchanged(served, name):
    """Spans and counting change no token: each request matches a
    sequential single-request ServeLoop."""
    params, reqs, _, out = served(name)
    for uid, (prompt, gen) in enumerate(reqs):
        loop = reuse_programs(ServeLoop(params, CONFIGS[name],
                                        max_seq=MAX_SEQ, dispatch="gather"))
        want = loop.run(jnp.asarray(prompt[None, :], jnp.int32), gen)[0]
        np.testing.assert_array_equal(out[uid], want)


def test_decode_record_ends_at_the_health_fetch(served):
    """``decode`` keeps its meaning (forward through the health fetch):
    it is what ``summary()`` and each token's latency read."""
    _, _, sched, _ = served("dense")
    decodes = [s for s in sched.stats if s.phase == "decode"]
    s = sched.summary()
    assert s["decode"]["calls"] == len(decodes)
    assert s["decode"]["seconds"] == pytest.approx(
        sum(d.seconds for d in decodes))
    by_step = {d.step: d.seconds for d in decodes}
    req = next(r for r in sched.finished if r.uid == 0)   # from tick 0
    assert req.latencies_s[1:] == [
        by_step[k] for k in sorted(by_step)][:len(req.latencies_s) - 1]


def test_a_failed_span_records_nothing(tiny):
    params, _ = tiny
    sched = ServeScheduler(params, TINY, max_seq=MAX_SEQ, max_slots=2,
                           dispatch="gather")
    with pytest.raises(ValueError):
        with sched._span("decode", 0):
            raise ValueError("boom")
    assert sched.stats == []


def test_profiler_sees_bare_span_names(tiny, tmp_path):
    from jax.profiler import ProfileData
    params, reqs = tiny
    sched = ServeScheduler(params, TINY, max_seq=MAX_SEQ, max_slots=2,
                           dispatch="gather")
    for prompt, gen in reqs[:2]:
        sched.submit(prompt, gen)
    sched.step()                          # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        sched.step()
        sched.step()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    names = [e.name for plane in ProfileData.from_file(files[0]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    assert names.count("serve.step") == 2
    assert {"serve.step", "serve.admit", "serve.decode", "serve.writeback",
            "serve.sample"} <= set(names)
    assert all(n.count(".") == 1 and "#" not in n for n in names), names
