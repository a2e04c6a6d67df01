"""Plain float32 reference of Qwen3-Next's forward pass (``qwen3_next``).

Straightforward ``jax.numpy`` over one token sequence: no kernels, no cache,
no batching, every product at ``HIGHEST`` precision (on a TPU a float32
matrix product is otherwise one bfloat16 pass).  It reads the program's
parameter tree (``models.model.init_params``) as data and follows the
published description, independently of ``models/`` code:

* token embedding; per layer RMSNorm, a token mixer, a residual add,
  RMSNorm, the MoE block, a residual add; final RMSNorm and the untied
  unembedding;
* the mixer of three layers in four is Gated DeltaNet (the equations in
  ``models/gdn.py``'s docstring), of the fourth full attention: q projection
  emitting [query | gate] per head, RMSNorm on each query and key head,
  RoPE on the first ``rope_fraction`` of each head's dims (rotate-half),
  causal softmax scaled by head_dim**-0.5 over grouped kv heads, the output
  times sigmoid(gate), the output projection;
* MoE: softmax over all router logits, top_k, the k weights renormalised;
  each routed expert a SwiGLU; plus the shared SwiGLU expert times
  sigmoid(x @ shared_gate).

Departures, each shared with the program:

* the expert share: of the ``n_experts`` the router scores, only the
  ``experts_held`` from ``expert_offset`` are computed; a token's weight on
  an expert held elsewhere contributes nothing (expert parallelism without
  its exchange);
* the RMSNorm weights are stored as the multiplier itself (the published
  checkpoint stores ``w - 1`` for its zero-centred norms), which is the same
  function;
* no multi-token-prediction module (not in ``config.json``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta, fraction):
    """x: (S, H, hd); rotate-half RoPE on the first ``fraction`` of dims."""
    rd = int(x.shape[-1] * fraction)
    inv = 1.0 / theta ** (np.arange(0, rd, 2, dtype=np.float32) / rd)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : rd // 2], x[..., rd // 2: rd]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rd:]],
                           -1)


def attention(w, x, cfg):
    S = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pos = jnp.arange(S)
    qg = _mm(x, w["wq"]).reshape(S, H, -1)
    q = qg[..., :hd]
    k = _mm(x, w["wk"]).reshape(S, K, hd)
    v = _mm(x, w["wv"]).reshape(S, K, hd)
    if cfg.qk_norm:
        q = _rms(q, w["q_norm"]["scale"], cfg.norm_eps)
        k = _rms(k, w["k_norm"]["scale"], cfg.norm_eps)
    q = _rope(q, pos, cfg.rope_theta, cfg.rope_fraction)
    k = _rope(k, pos, cfg.rope_theta, cfg.rope_fraction)
    k = jnp.repeat(k, H // K, axis=1)       # query head j reads kv head j // g
    v = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    if cfg.attn_output_gate:
        o = o * jax.nn.sigmoid(qg[..., hd:])
    return _mm(o.reshape(S, H * hd), w["wo"])


def gated_deltanet(w, x, cfg):
    S = x.shape[0]
    Hk, Hv = cfg.gdn_k_heads, cfg.gdn_v_heads
    Dk, Dv, r = cfg.gdn_k_head_dim, cfg.gdn_v_head_dim, Hv // Hk
    qkvz = _mm(x, w["in_proj_qkvz"]).reshape(S, Hk, 2 * Dk + 2 * r * Dv)
    ba = _mm(x, w["in_proj_ba"]).reshape(S, Hk, 2 * r)
    q, k = qkvz[..., :Dk], qkvz[..., Dk:2 * Dk]
    v = qkvz[..., 2 * Dk:2 * Dk + r * Dv].reshape(S, Hv, Dv)
    z = qkvz[..., 2 * Dk + r * Dv:].reshape(S, Hv, Dv)
    b, a = ba[..., :r].reshape(S, Hv), ba[..., r:].reshape(S, Hv)
    mixed = jnp.concatenate([q.reshape(S, -1), k.reshape(S, -1),
                             v.reshape(S, -1)], -1)
    W = cfg.gdn_conv
    padded = jnp.pad(mixed, ((W - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(padded[i:i + S] * w["conv_w"][i]
                           for i in range(W)))
    q = conv[:, :Hk * Dk].reshape(S, Hk, Dk)
    k = conv[:, Hk * Dk:2 * Hk * Dk].reshape(S, Hk, Dk)
    v = conv[:, 2 * Hk * Dk:].reshape(S, Hv, Dv)
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                      + 1e-6)
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
    o = _rms(o, w["norm"], cfg.norm_eps) * jax.nn.silu(z)
    return _mm(o.reshape(S, Hv * Dv), w["out_proj"])


def _swiglu(w_gate, w_up, w_down, x):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def moe(w, x, cfg):
    """Routed experts of the held share, plus the gated shared expert."""
    probs = jax.nn.softmax(_mm(x, w["router"]), -1)
    top, ids = jax.lax.top_k(probs, cfg.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    ex = w["experts"]
    out = jnp.zeros_like(x)
    for e in range(cfg.n_held):
        weight = jnp.sum(jnp.where(ids == cfg.expert_offset + e, top, 0.0),
                         -1)
        out = out + weight[:, None] * _swiglu(
            ex["w_gate"][e], ex["w_up"][e], ex["w_down"][e], x)
    if cfg.moe_shared_expert:
        sh = w["shared"]
        y = _swiglu(sh["w_gate"], sh["w_up"], sh["w_down"], x)
        if cfg.moe_shared_gate:
            y = y * jax.nn.sigmoid(_mm(x, w["shared_gate"]))
        out = out + y
    return out


def hidden(params, tokens, cfg):
    """Final-normed hidden states (S, d) of one token sequence."""
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(cfg.n_repeats):
        for slot, kind in enumerate(cfg.block_unit):
            w = jax.tree.map(lambda a: a[i], params["blocks"][slot])
            h = _rms(x, w["ln1"]["scale"], cfg.norm_eps)
            if kind == "gdn+moe":
                x = x + gated_deltanet(w["mixer"], h, cfg)
            elif kind == "attn+moe":
                x = x + attention(w["attn"], h, cfg)
            else:
                raise ValueError(f"not a qwen3_next layer kind: {kind}")
            x = x + moe(w["ffn"], _rms(x, w["ln2"]["scale"], cfg.norm_eps),
                        cfg)
    return _rms(x, params["final_norm"]["scale"], cfg.norm_eps)


def logits(params, tokens, cfg):
    """(S, vocab_size) float32 logits of one token sequence."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, cfg)
        unemb = (params["embed"].T if cfg.tie_embeddings
                 else params["unembed"])
        return _mm(h, unemb[:, :cfg.vocab_size])
