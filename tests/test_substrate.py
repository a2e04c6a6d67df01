"""Substrate tests: data determinism, optimizer, checkpointing (incl. crash
tolerance), gradient compression, fault-tolerant trainer restarts."""
import dataclasses
import functools
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import SyntheticLM, Prefetcher
from repro.grad_comp.sparse_allreduce import (compress, compression_ratio,
                                              sparse_allreduce_tree,
                                              union_reduce)
from repro.models import model as M
from repro.optim.adamw import AdamW, cosine_schedule, global_norm
from repro.runtime.trainer import (SimulatedFailure, Trainer, TrainerConfig,
                                   run_with_restarts)

CFG = get_smoke("qwen3-1.7b")


# ------------------------------------------------------------------ data ----

def test_data_step_addressable_determinism():
    d1 = SyntheticLM(CFG, batch=4, seq_len=32, seed=7)
    d2 = SyntheticLM(CFG, batch=4, seq_len=32, seed=7)
    np.testing.assert_array_equal(d1.batch_at(13)["tokens"],
                                  d2.batch_at(13)["tokens"])
    assert not np.array_equal(d1.batch_at(13)["tokens"],
                              d1.batch_at(14)["tokens"])


def test_data_prefetcher():
    d = SyntheticLM(CFG, batch=2, seq_len=16, seed=1)
    pf = Prefetcher(d.stream(), depth=2)
    b1 = next(pf)
    b2 = next(pf)
    assert b1["tokens"].shape == (2, 16)
    assert not np.array_equal(b1["tokens"], b2["tokens"])
    pf.close()


def test_data_learnable_structure():
    d = SyntheticLM(CFG, batch=8, seq_len=64, seed=3, noise=0.0)
    toks = d.batch_at(0)["tokens"]
    # with zero noise, t_{i+1} == perm[t_i] exactly
    np.testing.assert_array_equal(toks[:, 1:], d.perm[toks[:, :-1]])


# ------------------------------------------------------------- optimizer ----

def test_adamw_reduces_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0, clip_norm=None)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(jnp.asarray(0))) < 1e-4
    assert abs(float(lr(jnp.asarray(10))) - 1e-3) < 1e-4
    assert float(lr(jnp.asarray(100))) < 2e-4


# ------------------------------------------------------------ checkpoint ----

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    state = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    mgr.save(5, state, metadata={"loss": 1.5})
    like = jax.eval_shape(lambda: state)
    got, step = mgr.restore(like)
    assert step == 5
    np.testing.assert_array_equal(got["a"], state["a"])
    assert mgr.metadata(5)["loss"] == 1.5


def test_checkpoint_gc_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    state = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, state)
    assert sorted(mgr.all_steps()) == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_crash_tolerance(tmp_path):
    """A stale LATEST pointer (crash between rename and pointer write) must
    fall back to the newest complete step."""
    mgr = CheckpointManager(tmp_path, keep=3)
    state = {"x": jnp.zeros(2)}
    mgr.save(1, state)
    mgr.save(2, state)
    (tmp_path / "LATEST").write_text("99")      # corrupt pointer
    assert mgr.latest_step() == 2


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
sys.path.insert(0, "src")
from repro.checkpoint.manager import CheckpointManager
from repro.parallel.mesh import make_mesh

d = sys.argv[1]
mode = sys.argv[2]
mgr = CheckpointManager(d)
if mode == "save":
    mesh = make_mesh((4, 2), ("data", "model"))
    w = jax.device_put(np.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh, P("data", "model")))
    mgr.save(3, {"w": w})
else:  # restore on a DIFFERENT mesh shape
    mesh = make_mesh((2, 4), ("data", "model"))
    sh = {"w": NamedSharding(mesh, P("model", "data"))}
    like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float64)}
    got, step = mgr.restore(like, shardings=sh)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.arange(64.0).reshape(8, 8))
    print("ELASTIC_OK")
"""


def test_checkpoint_elastic_reshard(tmp_path):
    """Save on a 4x2 mesh, restore onto a 2x4 mesh with different specs."""
    env = dict(os.environ)
    for mode in ("save", "restore"):
        r = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT,
                            str(tmp_path), mode],
                           capture_output=True, text=True, env=env,
                           cwd=os.path.dirname(os.path.dirname(__file__)))
        assert r.returncode == 0, r.stderr
    assert "ELASTIC_OK" in r.stdout


# ------------------------------------------------------- grad compression ---

def test_topk_compress_error_feedback():
    g = jnp.asarray(np.random.default_rng(0).standard_normal(256), jnp.float32)
    keys, vals, err = compress(g, k=32)
    # kept + error reconstructs the gradient exactly
    from repro.core.su import stream_densify
    dense = stream_densify(keys, vals, jnp.asarray(32), 256)
    np.testing.assert_allclose(np.asarray(dense + err), np.asarray(g),
                               rtol=1e-6, atol=1e-6)


def test_union_reduce_equals_dense_sum():
    rng = np.random.default_rng(1)
    W, D, k = 4, 128, 16
    grads = rng.standard_normal((W, D)).astype(np.float32)
    keys = np.zeros((W, k), np.int32)
    vals = np.zeros((W, k), np.float32)
    dense_sum = np.zeros(D, np.float32)
    for w in range(W):
        idx = np.sort(rng.choice(D, k, replace=False)).astype(np.int32)
        keys[w], vals[w] = idx, grads[w, idx]
        dense_sum[idx] += grads[w, idx]
    ukeys, uvals, count = union_reduce(jnp.asarray(keys), jnp.asarray(vals))
    from repro.core.su import stream_densify
    got = stream_densify(ukeys, uvals, count, D)
    np.testing.assert_allclose(np.asarray(got), dense_sum, rtol=1e-5, atol=1e-5)


def test_sparse_allreduce_tree_mean():
    rng = np.random.default_rng(2)
    grads = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    dense, errs = sparse_allreduce_tree(grads, k=64)  # k=D -> lossless
    np.testing.assert_allclose(np.asarray(dense),
                               np.asarray(grads.mean(0)), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(errs).max()) < 1e-6


def test_compression_ratio_accounting():
    assert compression_ratio(D=10_000_000, k=10_000, workers=16) > 30


# ---------------------------------------------------------------- trainer ---

def _make_step(cfg, opt):
    @jax.jit
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, tokens, cfg))(params)
        new_p, new_o = opt.update(grads, opt_state, params)
        return new_p, new_o, {"loss": loss, "grad_norm": global_norm(grads)}
    return step


def _trainer(tmp, cfg, opt, total=12, hook=None):
    data = SyntheticLM(cfg, batch=2, seq_len=16, seed=0)
    return Trainer(
        TrainerConfig(total_steps=total, ckpt_every=4, ckpt_dir=str(tmp),
                      log_every=1000),
        cfg, _make_step(cfg, opt), opt, data,
        init_state=lambda: M.init_params(jax.random.PRNGKey(0), cfg),
        failure_hook=hook)


def test_trainer_runs_and_checkpoints(tmp_path):
    cfg = dataclasses.replace(CFG, policy="f32")
    opt = AdamW(lr=1e-3)
    out = _trainer(tmp_path, cfg, opt).run()
    assert len(out["history"]) == 12
    assert CheckpointManager(tmp_path).latest_step() == 11


def test_trainer_restart_identical_trajectory(tmp_path):
    """Two injected failures; the stitched loss history must equal an
    uninterrupted run's exactly (determinism across restarts)."""
    cfg = dataclasses.replace(CFG, policy="f32")
    opt = AdamW(lr=1e-3)

    ref = _trainer(tmp_path / "ref", cfg, opt).run()

    crashes = {5: True, 9: True}

    def hook(step):
        if crashes.pop(step, None):
            raise SimulatedFailure(f"injected at {step}")

    losses = {}

    def make():
        t = _trainer(tmp_path / "ft", cfg, opt, hook=hook)
        orig_run = t.run
        def run():
            out = orig_run()
            return out
        t.run = run
        trainers.append(t)
        return t

    trainers = []
    out = run_with_restarts(make)
    assert out["restarts"] == 2
    stitched = {}
    for t in trainers:
        for step, loss in t.history:
            stitched[step] = loss
    ref_losses = dict(ref["history"])
    # compare the overlap from the last restart onwards (all steps covered)
    assert set(stitched) == set(ref_losses)
    for s in ref_losses:
        assert abs(stitched[s] - ref_losses[s]) < 1e-5, (s, stitched[s],
                                                         ref_losses[s])
