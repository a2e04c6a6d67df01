"""The served weight tree: ``model.compute_params`` holds each matrix in the
policy's compute dtype once, when a serving driver takes the weights, and
leaves every float32-by-contract leaf wide.

On tiny ``bf16``-policy configs (dense, top-1 MoE with gather and with bcsr
dispatch, the hybrid Qwen3-Next stack): ``prefill`` and ``decode_step``
give bit-identical logits and caches on the cast tree and on the float32
tree, and ``ServeScheduler`` emits the same tokens as a scheduler stepping
the float32 tree; the decode program of the cast tree converts no weight;
each leaf sits in the dtype its use wants, and every leaf of a served tree
has a role.  An f32 policy gets its tree back leaf for leaf, quantized
experts come through untouched, and ``summary()["weights"]`` adds up to
the tree's bytes.  Tier-1, CPU.
"""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.core.precision import QuantTensor
from repro.launch.serve import ServeLoop, ServeScheduler
from repro.models import model as M
from repro.models import moe

from test_serve_inplace import DENSE, MOE, QWEN3_NEXT

BF16 = {"dense": dataclasses.replace(DENSE, policy="bf16"),
        "moe": dataclasses.replace(MOE, policy="bf16"),
        "qwen3-next": dataclasses.replace(
            get_smoke("qwen3-next-80b-a3b"), policy="bf16")}
# (config, dispatch) per case
CASES = {"dense": ("dense", "gather"), "moe-gather": ("moe", "gather"),
         "moe-bcsr": ("moe", "bcsr"), "qwen3-next": ("qwen3-next", "gather")}
MAX_SEQ = 24


def _cfg(case):
    name, dispatch = CASES[case]
    return dataclasses.replace(BF16[name], moe_dispatch=dispatch)


@pytest.fixture(scope="module")
def weights():
    """``weights(name) -> float32 tree``, each drawn on first use."""
    built = {}

    def get(name):
        if name not in built:
            built[name] = jax.jit(M.init_params, static_argnums=1)(
                jax.random.PRNGKey(0), BF16[name])
        return built[name]
    return get


def _name(path):
    return getattr(path[-1], "key", None)


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda a: isinstance(a, QuantTensor))[0]


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_and_decode_bit_identical(weights, case):
    """Logits and caches of a prefill and three decode steps at per-row
    positions are bit for bit the same on the cast tree as on float32."""
    cfg = _cfg(case)
    wide = weights(CASES[case][0])
    cast = M.compute_params(wide, cfg)
    prefill = jax.jit(lambda p, t: M.prefill(p, t, cfg, max_seq=MAX_SEQ))
    decode = jax.jit(lambda p, c, pos, t: M.decode_step(p, cfg, c, pos, t))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                              cfg.vocab_size)
    runs = []
    for params in (wide, cast):
        logits, cache, pos = prefill(params, toks)
        out = [(logits, cache)]
        pos = jnp.full((2,), pos, jnp.int32)
        for _ in range(3):
            tok = jnp.argmax(logits[:, -1:, :cfg.vocab_size], -1)
            logits, cache = decode(params, cache, pos, tok.astype(jnp.int32))
            out.append((logits, cache))
            pos = pos + 1
        runs.append(out)
    _assert_trees_equal(runs[0], runs[1])


def _serve(sched, cfg):
    rng = np.random.default_rng(5)
    for _ in range(3):
        sched.submit(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 9))),
                     int(rng.integers(3, 7)))
    return sched.run()


@pytest.mark.parametrize("case", list(CASES))
def test_scheduler_tokens_unchanged(weights, case):
    """The scheduler serving its cast tree emits the tokens of one whose
    ticks are handed the float32 tree (each use casting it again)."""
    cfg = _cfg(case)
    wide = weights(CASES[case][0])
    kw = dict(max_seq=MAX_SEQ, max_slots=2, dispatch=CASES[case][1])
    served = ServeScheduler(wide, cfg, **kw)
    assert any(a.dtype == jnp.bfloat16
               for a in jax.tree.leaves(served.params))
    per_use = ServeScheduler(wide, cfg, **kw)
    per_use.params = wide
    want = _serve(per_use, cfg)
    got = _serve(served, cfg)
    assert sorted(got) == sorted(want)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])


_CONVERT = re.compile(
    r"stablehlo\.convert[^\n]*: \(tensor<([0-9x]+)xf32>\) -> "
    r"tensor<([0-9x]+)xbf16>")


@pytest.mark.parametrize("case", list(CASES))
def test_decode_program_converts_no_weight(weights, case):
    """The decode program lowered on the cast tree holds no f32-to-bf16
    convert of a weight's shape (whole stack or one layer's slice); on the
    float32 tree it holds one for every matrix."""
    cfg = _cfg(case)
    wide = weights(CASES[case][0])
    shapes = set()
    for path, a in _leaves(wide):
        if _name(path) in M.COMPUTE_LEAVES:
            shapes |= {a.shape, a.shape[1:]}
    shapes = {"x".join(map(str, s)) for s in shapes if s}
    cache = M.init_cache(cfg, 2, MAX_SEQ)
    pos = jnp.zeros((2,), jnp.int32)
    tok = jnp.zeros((2, 1), jnp.int32)

    def weight_converts(params):
        text = jax.jit(lambda p: M.decode_step(p, cfg, cache, pos, tok)) \
            .lower(params).as_text()
        return [m.group(1) for m in _CONVERT.finditer(text)
                if m.group(1) == m.group(2) and m.group(1) in shapes]

    assert weight_converts(wide)
    assert weight_converts(M.compute_params(wide, cfg)) == []


@pytest.mark.parametrize("name", sorted(BF16))
def test_each_role_in_its_dtype(weights, name):
    """Matrices, biases and embeddings in the compute dtype; norm scales,
    the router and the Gated DeltaNet vectors, taps and norm float32."""
    cast = M.compute_params(weights(name), BF16[name])
    seen = set()
    for path, a in _leaves(cast):
        key = _name(path)
        seen.add(key)
        want = jnp.bfloat16 if key in M.COMPUTE_LEAVES else jnp.float32
        assert a.dtype == want, jax.tree_util.keystr(path)
    assert {"wq", "wo", "w_gate", "w_down", "embed", "scale"} <= seen
    if name == "moe":
        assert "router" in seen
    if name == "qwen3-next":
        assert {"router", "A_log", "dt_bias", "conv_w", "norm",
                "in_proj_qkvz", "out_proj", "shared_gate", "unembed"} <= seen


@pytest.mark.parametrize("name", sorted(BF16))
def test_every_leaf_has_a_role(weights, name):
    """A served tree holds no leaf the two rules do not name, so a new
    weight leaf forces a decision on its dtype."""
    roles = M.COMPUTE_LEAVES | M.WIDE_LEAVES
    assert not M.COMPUTE_LEAVES & M.WIDE_LEAVES
    for path, _ in _leaves(weights(name)):
        assert _name(path) in roles, jax.tree_util.keystr(path)


@pytest.mark.parametrize("name", sorted(BF16))
def test_f32_policy_returns_the_tree(weights, name):
    wide = weights(name)
    cfg = dataclasses.replace(BF16[name], policy="f32")
    cast = M.compute_params(wide, cfg)
    assert jax.tree.structure(cast) == jax.tree.structure(wide)
    assert all(a is b for a, b in zip(jax.tree.leaves(cast),
                                      jax.tree.leaves(wide)))
    s = ServeLoop(wide, cfg, max_seq=MAX_SEQ).summary()["weights"]
    assert s["cast_leaves"] == 0 and s["wide_bytes"] == 0


def test_quantized_experts_come_through(weights):
    """``quantize_experts`` runs first; its QuantTensor leaves reach the
    served tree as they left it, and the rest is cast."""
    cfg = BF16["moe"]
    wide = weights("moe")
    quant = moe.quantize_model_experts(wide, "int8")
    sched = ServeScheduler(wide, cfg, max_seq=MAX_SEQ, max_slots=2,
                           quantize_experts="int8")
    experts = [(p, a) for p, a in _leaves(sched.params)
               if isinstance(a, QuantTensor)]
    assert experts
    want = {jax.tree_util.keystr(p): a for p, a in _leaves(quant)
            if isinstance(a, QuantTensor)}
    assert {jax.tree_util.keystr(p) for p, _ in experts} == set(want)
    for p, a in experts:
        _assert_trees_equal(a, want[jax.tree_util.keystr(p)])
    assert sched.params["blocks"][1]["ffn"]["shared"]["w_up"].dtype \
        == jnp.bfloat16
    assert _serve(sched, cfg)


@pytest.mark.parametrize("driver", ["scheduler", "loop"])
@pytest.mark.parametrize("name", sorted(BF16))
def test_summary_weights_add_up(weights, name, driver):
    cfg = BF16[name]
    wide = weights(name)
    if driver == "scheduler":
        d = ServeScheduler(wide, cfg, max_seq=MAX_SEQ, max_slots=2)
    else:
        d = ServeLoop(wide, cfg, max_seq=MAX_SEQ)
    s = d.summary()["weights"]
    leaves = jax.tree.leaves(d.params)
    assert s["compute_bytes"] + s["wide_bytes"] == sum(a.nbytes
                                                       for a in leaves)
    assert s["compute_bytes"] == sum(a.nbytes for a in leaves
                                     if a.dtype == jnp.bfloat16)
    n_cast = sum(_name(p) in M.COMPUTE_LEAVES for p, _ in _leaves(wide))
    assert s["cast_leaves"] == n_cast > 0
    # the wide remainder is what the rules keep in float32
    assert s["wide_bytes"] == sum(a.nbytes for p, a in _leaves(wide)
                                  if _name(p) in M.WIDE_LEAVES)
