"""ServeScheduler's donated tick: the fused path hands the whole slot pool
to ``jit_decode_step``, which writes the step's rows back inside the
program, and ``serve.writeback`` only commits the pool it returns.

Checked on the CPU, where donation is real (a donated leaf reports
``is_deleted()``): the pool a tick was given is gone after it; the
``writeback`` span commits the very pool the program returned; tokens and
the final pool are identical to an undonated tick (an eager slice,
``_decode_fused``, an eager scatter), for a dense stack and for Qwen3-Next
(Gated DeltaNet conv tail and state, the held-pair leaf); and rows past
the step's batch bucket come back bit for bit, on the dense, top-1 MoE and
hybrid stacks.
Tier-1, tiny configs.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.launch import serve
from repro.launch.serve import ServeScheduler
from repro.models import model as M
from repro.models.config import ArchConfig

DENSE = ArchConfig(
    name="tiny-inplace", family="dense", d_model=32, n_heads=2, n_kv_heads=1,
    d_ff=48, vocab_size=64, block_unit=("attn",), n_repeats=2, head_dim=16,
    policy="f32")
MOE = ArchConfig(
    name="tiny-inplace-moe", family="moe", d_model=32, n_heads=2,
    n_kv_heads=1, d_ff=48, vocab_size=64, block_unit=("attn", "attn+moe"),
    n_repeats=2, head_dim=16, n_experts=4, top_k=1, capacity_factor=1.0,
    moe_shared_expert=True, policy="f32")
QWEN3_NEXT = dataclasses.replace(get_smoke("qwen3-next-80b-a3b"),
                                 policy="f32")
CONFIGS = {"dense": DENSE, "qwen3-next": QWEN3_NEXT}
MAX_SEQ = 24

_PROGRAMS = {}


def reuse_programs(driver):
    """Give a new ``ServeScheduler`` or ``ServeLoop`` the compiled decode
    programs of the first driver of its kind built for the same config and
    dispatch backend, the two things its trace bakes in (cache and
    parameter structures key jit's own cache), so a test suite that builds
    many drivers compiles each tick once.  Returns ``driver``."""
    key = (type(driver), driver.cfg, driver.backend)
    names = [n for n in ("_decode_fused", "_decode_inplace")
             if hasattr(driver, n)]
    progs = _PROGRAMS.setdefault(key, {n: getattr(driver, n) for n in names})
    for n in names:
        setattr(driver, n, progs[n])
    return driver


@pytest.fixture(scope="module")
def weights():
    return {name: jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg) for name, cfg in
        {**CONFIGS, "moe": MOE}.items()}


def _requests(cfg, gens, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 10))), g)
            for g in gens]


def _sched(params, cfg, slots=2, **kw):
    return reuse_programs(ServeScheduler(
        params, cfg, max_seq=MAX_SEQ, max_slots=slots,
        cache_dtype=jnp.float32, **kw))


def _undonated(sched, monkeypatch):
    """Give ``sched`` the tick it had before the pool was donated: the
    step's rows sliced eagerly, the undonated ``_decode_fused``, the new
    rows scattered back eagerly; and the eager admission scatter."""
    def step(params, pool, pos, tokens, *, bucket):
        rows = jax.tree.map(lambda a: a[:, :bucket], pool)
        logits, new, held = sched._decode_fused(params, rows, pos, tokens)
        pool = jax.tree.map(lambda big, small: big.at[:, :bucket].set(
            small.astype(big.dtype)), pool, new)
        return logits, pool, held

    def commit_row(pool, row_cache, slot):
        return jax.tree.map(lambda big, small: big.at[:, slot].set(
            small[:, 0].astype(big.dtype)), pool, row_cache)

    sched._decode_inplace = step
    monkeypatch.setattr(serve, "_commit_row", commit_row)
    return sched


def _assert_pools_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fused_tick_consumes_the_pool_it_was_given(weights):
    sched = _sched(weights["dense"], DENSE)
    for prompt, gen in _requests(DENSE, (4, 4)):
        sched.submit(prompt, gen)
    sched.admit()
    before = sched.cache
    sched.decode_step()
    assert all(a.is_deleted() for a in jax.tree.leaves(before))
    assert not any(a.is_deleted() for a in jax.tree.leaves(sched.cache))
    sched.run()                       # the committed pool serves on
    assert len(sched.finished) == 2


@pytest.mark.parametrize("dispatch", ["gather", "bcsr"])
def test_writeback_records_the_path(weights, dispatch):
    """One ``writeback`` record a tick, after its ``decode``, and what it
    commits is the pool ``jit_decode_step`` returned, not a copy."""
    sched = _sched(weights["moe"], MOE, dispatch=dispatch)
    returned = []
    step = sched._decode_inplace

    def recording(*a, **k):
        out = step(*a, **k)
        returned.append(out[1])
        return out
    sched._decode_inplace = recording
    for prompt, gen in _requests(MOE, (3, 5)):
        sched.submit(prompt, gen)
    while sched.has_work():
        sched.step()
        assert sched.cache is returned[-1]
    phases = [s.phase for s in sched.stats
              if s.phase in ("decode", "writeback")]
    assert phases == ["decode", "writeback"] * len(returned)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tokens_and_pool_match_the_undonated_tick(weights, monkeypatch,
                                                  name):
    """Join, evict and refill on two slots: the donated tick and the
    undonated one give the same tokens and leave the same pool."""
    cfg = CONFIGS[name]
    reqs = _requests(cfg, (6, 3, 5))

    def serve_all(sched):
        for prompt, gen in reqs:
            sched.submit(prompt, gen)
        return sched, sched.run()

    got_s, got = serve_all(_sched(weights[name], cfg))
    want_s, want = serve_all(_undonated(_sched(weights[name], cfg),
                                        monkeypatch))
    assert sorted(got) == sorted(want) == [0, 1, 2]
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    _assert_pools_equal(got_s.cache, want_s.cache)
    if name == "qwen3-next":
        held = [s.extra["moe_held_pairs"] for s in got_s.stats
                if s.phase == "decode"]
        assert held == [s.extra["moe_held_pairs"] for s in want_s.stats
                        if s.phase == "decode"]


@pytest.mark.parametrize("residents", [1, 2])
@pytest.mark.parametrize("name", ["dense", "moe", "qwen3-next"])
def test_rows_past_the_bucket_come_back_bit_for_bit(weights, name,
                                                    residents):
    """Four slots, ``residents`` of them decoding (bucket 1 or 2): the rows
    past the bucket, filled with noise in every leaf, are left as they
    were."""
    cfg = {**CONFIGS, "moe": MOE}[name]
    sched = _sched(weights[name], cfg, slots=4)
    for prompt, gen in _requests(cfg, (5,) * residents):
        sched.submit(prompt, gen)
    sched.admit()
    assert len(sched.active) == residents
    rng = np.random.default_rng(11)

    def noise(a):
        shape = (a.shape[0], a.shape[1] - residents) + a.shape[2:]
        fill = (rng.integers(1, 9, shape) if a.dtype == jnp.int32
                else rng.standard_normal(shape))
        return a.at[:, residents:].set(jnp.asarray(fill, a.dtype))

    sched.cache = jax.tree.map(noise, sched.cache)
    past = [np.asarray(a[:, residents:]) for a in jax.tree.leaves(sched.cache)]
    sched.decode_step()
    step = next(s for s in sched.stats if s.phase == "decode")
    assert step.extra["batch_bucket"] == residents
    for a, p in zip(jax.tree.leaves(sched.cache), past):
        np.testing.assert_array_equal(np.asarray(a[:, residents:]), p)
