"""Plain float32 forward pass of a dense Qwen3-style decoder, independent of
the program under test.

It follows the published description of the architecture: token embedding;
per layer, RMSNorm, grouped-query attention with RMSNorm on each query and
key head, rotary position embedding (the two halves of each head rotated,
base ``rope_theta``), a causal softmax scaled by head_dim**-0.5 and the
output projection, a residual add, RMSNorm, a SwiGLU MLP and a residual add;
a final RMSNorm and the unembedding (the embedding, transposed, where the
configuration ties them).  Every product is float32 at ``HIGHEST`` precision
(on a TPU a float32 matrix product is otherwise one bfloat16 pass).

The weights are the benchmark's own (``drivers/serve.make_weights``), in the
layout named there.  ``quant=True`` makes the control: the same pass with
the operands of every product rounded to float8 e4m3 (one scale per row of
the left operand and per column of the right one), the precision below the
bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


class Dims(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    theta: float
    eps: float

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(cfg["num_hidden_layers"], cfg["hidden_size"],
                   cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"], cfg["intermediate_size"],
                   cfg["vocab_size"], float(cfg["rope_theta"]),
                   float(cfg["rms_norm_eps"]))


def _e4m3(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(a, b, quant: bool):
    """a @ b over the last axis of ``a`` and the first of ``b``."""
    if quant:
        a, b = _e4m3(a, -1), _e4m3(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, hd); the first and second halves of each head rotate."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def layer(x, lw, i, *, dims: Dims, quant: bool):
    """Layer ``i`` of the stack ``lw`` over one sequence x: (S, d)."""
    w = jax.tree.map(lambda a: a[i], lw)
    S = x.shape[0]
    H, K, hd = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], dims.eps)
    q = _mm(h, w["wq"], quant).reshape(S, H, hd)
    k = _mm(h, w["wk"], quant).reshape(S, K, hd)
    v = _mm(h, w["wv"], quant).reshape(S, K, hd)
    q = _rope(_rms(q, w["q_norm"], dims.eps), pos, dims.theta)
    k = _rope(_rms(k, w["k_norm"], dims.eps), pos, dims.theta)
    g = H // K
    k = jnp.repeat(k, g, axis=1)            # query head j reads kv head j // g
    v = jnp.repeat(v, g, axis=1)
    if quant:
        q, k, v = _e4m3(q, -1), _e4m3(k, -1), _e4m3(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        p = _e4m3(p, -1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(S, H * hd)
    x = x + _mm(a, w["wo"], quant)
    h = _rms(x, w["ln2"], dims.eps)
    f = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(f, w["w_down"], quant)


def hidden(w, tokens, dims: Dims, quant: bool = False):
    """Final-normed hidden states (S, d) of one token sequence.  Padding it
    at the end changes no earlier position (attention is causal), so
    sequences padded to one length share one compiled layer."""
    x = w["embed"][jnp.asarray(tokens)]
    for i in range(dims.layers):
        x = layer(x, w["layers"], i, dims=dims, quant=quant)
    return _rms(x, w["final_norm"], dims.eps)


@functools.partial(jax.jit, static_argnames=("vocab", "quant"))
def _logits(h, embed, *, vocab: int, quant: bool):
    return _mm(h, embed[:vocab].T, quant)


def served_gaps(w, prompt, served, dims: Dims, *, pad_to: int,
                control: bool = False, block: int = 256) -> np.ndarray:
    """For each served token, how far the float32 reference's logit of the
    token lies below its best logit at that position (>= 0; 0 where the
    reference agrees).  With ``control`` the token is the one the float8
    pass puts first, not the served one.  ``prompt`` and ``served`` are
    int arrays; the sequence fed is prompt + served[:-1], padded with token
    0 to ``pad_to``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens > pad_to {pad_to}")
    seq = np.pad(seq, (0, pad_to - len(seq)))
    first = len(prompt) - 1                 # position predicting served[0]
    rows = slice(first, first + len(served))
    n = len(served)
    tail = -n % block                       # whole blocks: one program

    def rows_of(quant):
        h = hidden(w, seq, dims, quant=quant)[rows]
        return jnp.pad(h, ((0, tail), (0, 0)))
    h = rows_of(False)
    hq = rows_of(True) if control else None
    picks = np.pad(served, (0, tail))
    gaps = []
    for a in range(0, n + tail, block):
        lg = _logits(h[a:a + block], w["embed"], vocab=dims.vocab,
                     quant=False)
        if control:
            pick = jnp.argmax(_logits(hq[a:a + block], w["embed"],
                                      vocab=dims.vocab, quant=True), -1)
        else:
            pick = jnp.asarray(picks[a:a + block])
        got = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        gaps.append(np.asarray(lg.max(-1) - got))
    return np.concatenate(gaps)[:n]
