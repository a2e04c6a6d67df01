"""Integration tests: kernel-path wiring, prefill->decode handoff, dry-run
machinery on a tiny in-process mesh (subprocess), grad-compressed training.

Slow tier (model compiles + subprocess dry-runs): deselected from the
default run, enable with ``--run-slow`` (see tests/README.md)."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import model as M

pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


def test_model_forward_kernel_impl_matches_chunked():
    """The Pallas flash kernel (interpret mode) wired through the full model
    must match the chunked-jnp path."""
    cfg = dataclasses.replace(get_smoke("qwen3-1.7b"), policy="f32")
    params = M.init_params(KEY, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    # interpret=True is the default lowering on CPU inside the kernel wrapper
    import repro.kernels.flash_attention.ops as fops
    orig = fops.attention

    def interp_attention(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    fops.attention = interp_attention
    try:
        lk = M.forward(params, tokens, cfg, impl="kernel")
    finally:
        fops.attention = orig
    lc = M.forward(params, tokens, cfg, impl="chunked")
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lc), atol=2e-3,
                               rtol=2e-3)


@pytest.mark.parametrize("name", ["qwen2-1.5b", "zamba2-1.2b", "rwkv6-7b",
                                  "gemma3-12b", "llama4-scout-17b-a16e"])
def test_prefill_then_decode_matches_full_forward(name):
    """prefill(prompt) -> decode_step xN must equal teacher-forced forward."""
    cfg = get_smoke(name)
    params = M.init_params(KEY, cfg)
    B, S_prompt, S_gen = 1, 8, 6
    S = S_prompt + S_gen
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                cfg.vocab_size)
    full = M.forward(params, tokens, cfg)

    logits, cache, pos = M.prefill(params, tokens[:, :S_prompt], cfg,
                                   max_seq=S)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, S_prompt - 1]),
                               atol=8e-2, rtol=8e-2)
    outs = []
    for t in range(S_prompt, S):
        step_logits, cache = M.decode_step(
            params, cfg, cache, jnp.asarray(t, jnp.int32), tokens[:, t: t + 1])
        outs.append(step_logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray(full[:, S_prompt:]),
                               atol=8e-2, rtol=8e-2)


DRYRUN_SMOKE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
import dataclasses
from repro.configs import get_smoke
from repro.launch import steps as St
from repro.launch.shapes import ShapeSpec
from repro.launch.hlo_analysis import analyze

from repro.parallel.mesh import make_mesh

cfg = get_smoke("llama4-scout-17b-a16e")
shape = ShapeSpec("tiny_train", "train", 32, 8)
mesh = make_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    opt = St.default_optimizer()
    step, (p_s, o_s, tok_s, emb_s), out_s = St.make_train_step(
        cfg, shape, mesh, opt, seq_chunk=16)
    params = St.abstract_params(cfg)
    ps = jax.tree.map(lambda s: NamedSharding(mesh, s), p_s,
                      is_leaf=lambda x: isinstance(x, P))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params, ps, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    opt_state = jax.eval_shape(opt.init, params)
    os_ = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       St.AdamWState(m=p_s, v=p_s, count=P(), master=None),
                       is_leaf=lambda x: isinstance(x, P))
    opt_state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        opt_state, os_, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    tokens = jax.ShapeDtypeStruct((8, 32), jax.numpy.int32,
                                  sharding=NamedSharding(mesh, tok_s))
    compiled = jax.jit(step).lower(params, opt_state, tokens).compile()
    acc = analyze(compiled.as_text())
    assert acc["dot_flops"] > 0
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    print("DRYRUN_SMOKE_OK", int(acc["dot_flops"]))
"""


def test_dryrun_machinery_small_mesh():
    """Full dry-run path (train step, shardings, HLO accounting) on an
    8-device fake mesh in a subprocess (keeps this process at 1 device)."""
    r = subprocess.run([sys.executable, "-c", DRYRUN_SMOKE],
                       capture_output=True, text=True,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DRYRUN_SMOKE_OK" in r.stdout


def test_grad_compressed_training_learns():
    """Top-k sparse-gradient training (SU union path) still reduces loss."""
    from repro.data.pipeline import SyntheticLM
    from repro.launch.train import make_step
    from repro.optim.adamw import AdamW
    cfg = dataclasses.replace(get_smoke("qwen3-1.7b"), policy="f32")
    opt = AdamW(lr=3e-3)
    step = make_step(cfg, opt, grad_compress_k=2048)
    params = M.init_params(KEY, cfg)
    state = opt.init(params)
    data = SyntheticLM(cfg, batch=4, seq_len=32, seed=0, noise=0.0)
    losses = []
    for i in range(30):
        b = data.batch_at(i)
        params, state, metrics = step(params, state, jnp.asarray(b["tokens"]))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


CVJP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.models.layers import chunked_attention, flash_fwd_chunked_bwd
from repro.parallel import context as pctx
from repro.parallel.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((2, 4, 256, 32)), jnp.float32)
k = jnp.asarray(rng.standard_normal((2, 2, 256, 32)), jnp.float32)
v = jnp.asarray(rng.standard_normal((2, 2, 256, 32)), jnp.float32)
with jax.set_mesh(mesh):
    with pctx.activation_specs(mesh=mesh):
        f = flash_fwd_chunked_bwd(True, None)
        gk = jax.grad(lambda q, k, v: (f(q, k, v) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        gc = jax.grad(lambda q, k, v: (chunked_attention(
            q, k, v, causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gc):
            assert float(jnp.abs(a - b).max()) < 2e-3
print("CVJP_OK")
"""


def test_flash_fwd_chunked_bwd_grads_match():
    """Kernel-forward/chunked-backward custom_vjp == pure-chunked grads
    (run on a fake 8-device mesh in a subprocess)."""
    r = subprocess.run([sys.executable, "-c", CVJP_SCRIPT],
                       capture_output=True, text=True,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CVJP_OK" in r.stdout
