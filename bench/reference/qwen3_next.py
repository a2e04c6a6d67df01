"""Plain float32 forward pass of Qwen3-Next (``qwen3_next``), independent of
the program under test, computed in blocks so that it fits one chip at the
published widths.

It follows the published description: token embedding; per layer RMSNorm,
a token mixer, a residual add, RMSNorm, the MoE block, a residual add; a
final RMSNorm and the untied unembedding.  Three layers in four mix tokens
with Gated DeltaNet:

* ``in_proj_qkvz`` and ``in_proj_ba`` laid out per key head as
  ``[q | k | v (r heads) | z (r heads)]`` and ``[b (r) | a (r)]``;
* a causal depthwise conv of width 4 and SiLU over the q || k || v channels;
* ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``;
* q and k L2-normalised, repeated to the value heads, q scaled by
  Dk**-0.5; per head ``S <- exp(g) S``, ``S <- S + k (x) beta (v - S^T k)``,
  ``o = S^T q``, from a zero state;
* ``RMSNorm(o) * w * silu(z)``, then ``out_proj``.

The fourth layer is full attention: the q projection emits [query | gate]
per head, RMSNorm on each query and key head, RoPE on the first quarter of
each head's dims (rotate-half, base ``rope_theta``), a causal softmax scaled
by head_dim**-0.5 over grouped kv heads, the output times sigmoid(gate), the
output projection.  The MoE block: softmax over all published router
logits, the top ``num_experts_per_tok``, their weights renormalised; each
routed expert a SwiGLU of width ``moe_intermediate_size``; plus a SwiGLU
shared expert times sigmoid(x @ shared_gate).  Every product is float32 at
``HIGHEST`` precision.

The expert share is the program's: of the router's experts, only the
``experts_held`` from ``expert_offset`` are computed (the configuration's
``deployment``), and a token's weight on an expert held elsewhere adds
nothing.  Blocks: attention by 512 query rows, the logits by 256 rows; the
recurrence is a scan over positions.

The weights are the benchmark's own (``drivers/serve_qwen3_next.py``), in
the program's parameter tree.  ``quant=True`` makes the control: the same
pass with the operands of every projection rounded to float8 e4m3 (one
scale per row of the left operand and per column of the right one), the
precision below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.decoder import _e4m3

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    periods: int          # layers / full_attention_interval
    gdn_per_period: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary: int           # rotated dims of each attention head
    k_heads: int          # Gated DeltaNet key heads
    v_heads: int
    k_dim: int
    v_dim: int
    conv: int
    experts: int          # the router's outputs
    held: int
    offset: int
    top_k: int
    vocab: int
    theta: float
    eps: float

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        n = cfg["full_attention_interval"]
        dep = cfg["deployment"]
        return cls(cfg["num_hidden_layers"] // n, n - 1, cfg["hidden_size"],
                   cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"],
                   int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
                   cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
                   cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
                   cfg["linear_conv_kernel_dim"], dep["router_experts"],
                   cfg["num_experts"], dep["expert_offset"],
                   cfg["num_experts_per_tok"], cfg["vocab_size"],
                   float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]))


def _mm(a, b, quant: bool):
    """a @ b over the last axis of ``a`` and the first of ``b``."""
    if quant:
        a, b = _e4m3(a, -1), _e4m3(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta, rd):
    inv = 1.0 / theta ** (np.arange(0, rd, 2, dtype=np.float32) / rd)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : rd // 2], x[..., rd // 2: rd]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rd:]],
                           -1)


def _attention(w, x, dims: Dims, quant: bool, block: int = 512):
    S = x.shape[0]
    H, K, hd = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(S)
    qg = _mm(x, w["wq"], quant).reshape(S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _mm(x, w["wk"], quant).reshape(S, K, hd)
    v = _mm(x, w["wv"], quant).reshape(S, K, hd)
    q = _rope(_rms(q, w["q_norm"]["scale"], dims.eps), pos, dims.theta,
              dims.rotary)
    k = _rope(_rms(k, w["k_norm"]["scale"], dims.eps), pos, dims.theta,
              dims.rotary)
    k = jnp.repeat(k, H // K, axis=1)       # query head j reads kv head j // g
    v = jnp.repeat(v, H // K, axis=1)
    if quant:
        q, k, v = _e4m3(q, -1), _e4m3(k, -1), _e4m3(v, 0)
    outs = []
    for a in range(0, S, block):
        qb = q[a:a + block]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * hd ** -0.5
        qpos = pos[a:a + block]
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = _e4m3(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    o = jnp.concatenate(outs, 0) * jax.nn.sigmoid(gate)
    return _mm(o.reshape(S, H * hd), w["wo"], quant)


def _gated_deltanet(w, x, dims: Dims, quant: bool):
    S = x.shape[0]
    Hk, Hv, Dk, Dv = dims.k_heads, dims.v_heads, dims.k_dim, dims.v_dim
    r = Hv // Hk
    qkvz = _mm(x, w["in_proj_qkvz"], quant).reshape(S, Hk,
                                                    2 * Dk + 2 * r * Dv)
    ba = _mm(x, w["in_proj_ba"], quant).reshape(S, Hk, 2 * r)
    q, k = qkvz[..., :Dk], qkvz[..., Dk:2 * Dk]
    v = qkvz[..., 2 * Dk:2 * Dk + r * Dv].reshape(S, Hv, Dv)
    z = qkvz[..., 2 * Dk + r * Dv:].reshape(S, Hv, Dv)
    b, a = ba[..., :r].reshape(S, Hv), ba[..., r:].reshape(S, Hv)
    mixed = jnp.concatenate([q.reshape(S, -1), k.reshape(S, -1),
                             v.reshape(S, -1)], -1)
    W = dims.conv
    padded = jnp.pad(mixed, ((W - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(padded[i:i + S] * w["conv_w"][i]
                           for i in range(W)))
    q = conv[:, :Hk * Dk].reshape(S, Hk, Dk)
    k = conv[:, Hk * Dk:2 * Hk * Dk].reshape(S, Hk, Dk)
    v = conv[:, 2 * Hk * Dk:].reshape(S, Hv, Dv)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(l2(q), r, axis=1) * Dk ** -0.5   # value head h: key h // r
    k = jnp.repeat(l2(k), r, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])

    def step(St, inp):
        qt, kt, vt, gt, bt = inp
        St = St * jnp.exp(gt)[:, None, None]
        kv = jnp.einsum("hkv,hk->hv", St, kt, precision=HIGHEST)
        St = St + jnp.einsum("hk,hv->hkv", kt, (vt - kv) * bt[:, None],
                             precision=HIGHEST)
        return St, jnp.einsum("hkv,hk->hv", St, qt, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((Hv, Dk, Dv), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, w["norm"], dims.eps) * jax.nn.silu(z)
    return _mm(o.reshape(S, Hv * Dv), w["out_proj"], quant)


def _swiglu(w_gate, w_up, w_down, x, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def _moe(w, x, dims: Dims, quant: bool):
    probs = jax.nn.softmax(_mm(x, w["router"], quant), -1)
    top, ids = jax.lax.top_k(probs, dims.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    ex = w["experts"]
    out = jnp.zeros_like(x)
    for e in range(dims.held):
        weight = jnp.sum(jnp.where(ids == dims.offset + e, top, 0.0), -1)
        out = out + weight[:, None] * _swiglu(
            ex["w_gate"][e], ex["w_up"][e], ex["w_down"][e], x, quant)
    sh = w["shared"]
    y = _swiglu(sh["w_gate"], sh["w_up"], sh["w_down"], x, quant)
    return out + y * jax.nn.sigmoid(_mm(x, w["shared_gate"], quant))


@functools.partial(jax.jit, static_argnames=("slot", "dims", "quant"))
def layer(x, blocks, i, *, slot: int, dims: Dims, quant: bool):
    """Layer ``i`` of block slot ``slot`` (Gated DeltaNet for the first
    ``gdn_per_period`` slots, attention for the last) over x: (S, d)."""
    w = jax.tree.map(lambda a: a[i], blocks[slot])
    h = _rms(x, w["ln1"]["scale"], dims.eps)
    if slot < dims.gdn_per_period:
        x = x + _gated_deltanet(w["mixer"], h, dims, quant)
    else:
        x = x + _attention(w["attn"], h, dims, quant)
    return x + _moe(w["ffn"], _rms(x, w["ln2"]["scale"], dims.eps), dims,
                    quant)


def hidden(params, tokens, dims: Dims, quant: bool = False):
    """Final-normed hidden states (S, d) of one token sequence.  Padding it
    at the end changes no earlier position (every mixer is causal), so
    sequences padded to one length share one compiled layer."""
    x = params["embed"][jnp.asarray(tokens)]
    for i in range(dims.periods):
        for slot in range(dims.gdn_per_period + 1):
            x = layer(x, params["blocks"], i, slot=slot, dims=dims,
                      quant=quant)
    return _rms(x, params["final_norm"]["scale"], dims.eps)


@functools.partial(jax.jit, static_argnames=("vocab", "quant"))
def _logits(h, unembed, *, vocab: int, quant: bool):
    return _mm(h, unembed[:, :vocab], quant)


def logit_rows(params, seq, rows, dims: Dims, *, pad_to: int,
               quant: bool = False, block: int = 256):
    """Yields the reference's logits (block, vocab) of positions ``rows``
    (a slice) of the token sequence ``seq``, padded with token 0 to
    ``pad_to``, ``block`` rows at a time (the last block padded)."""
    seq = np.asarray(seq, np.int32)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens > pad_to {pad_to}")
    h = hidden(params, np.pad(seq, (0, pad_to - len(seq))), dims,
               quant=quant)[rows]
    n = h.shape[0]
    h = jnp.pad(h, ((0, -n % block), (0, 0)))
    for a in range(0, n, block):
        yield _logits(h[a:a + block], params["unembed"], vocab=dims.vocab,
                      quant=quant)
