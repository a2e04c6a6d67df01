"""Gated DeltaNet mixer: Qwen3-Next's linear-attention layer.

Per token, with ``Hk`` key heads and ``Hv`` value heads (``r = Hv / Hk``):

* ``in_proj_qkvz`` (d -> 2 Hk Dk + 2 Hv Dv) and ``in_proj_ba`` (d -> 2 Hv),
  both laid out per key head as ``qwen3_next`` lays them out: key head j
  owns ``[q (Dk) | k (Dk) | v (r Dv) | z (r Dv)]`` and ``[b (r) | a (r)]``;
* a causal depthwise conv (width ``gdn_conv``, no bias) and SiLU over the
  q || k || v channels;
* ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``;
* q and k L2-normalised, repeated to the Hv value heads (value head h reads
  key head h // r), and q scaled by Dk**-0.5;
* per value head, the gated delta rule on a (Dk, Dv) state:
  ``S <- exp(g) S``, ``S <- S + k (x) (beta (v - S^T k))``, ``o = S^T q``;
* ``RMSNorm(o) * w * silu(z)`` per head, then ``out_proj`` (Hv Dv -> d).

The state and the recurrence are float32.  Decode (one token, a cache) runs
the step as the Pallas kernel ``kernels/gdn`` (``gdn_decode`` in the device
trace); prefill runs it as a ``lax.scan`` over positions.  The per-row
decode cache is ``conv`` (B, gdn_conv - 1, channels), the last inputs of
the conv, and ``state`` (B, Hv, Dk, Dv) float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig


def dims(cfg: ArchConfig):
    """(Hk, Hv, Dk, Dv, conv channels)."""
    Hk, Hv = cfg.gdn_k_heads, cfg.gdn_v_heads
    Dk, Dv = cfg.gdn_k_head_dim, cfg.gdn_v_head_dim
    return Hk, Hv, Dk, Dv, 2 * Hk * Dk + Hv * Dv


def init_gdn(key, cfg: ArchConfig):
    """Projections normal with variance 1/fan-in; ``A_log`` = log U(0, 16)
    and ``dt_bias`` the inverse softplus of a dt log-uniform in [1e-3, 0.1]
    (the Gated DeltaNet initialisation), so that decays span short and long
    memories."""
    d = cfg.d_model
    Hk, Hv, Dk, Dv, C = dims(cfg)
    ks = jax.random.split(key, 6)
    A = jax.random.uniform(ks[3], (Hv,), jnp.float32, 1e-3, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[4], (Hv,), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "in_proj_qkvz": jax.random.normal(
            ks[0], (d, 2 * Hk * Dk + 2 * Hv * Dv), jnp.float32) * d ** -0.5,
        "in_proj_ba": jax.random.normal(ks[1], (d, 2 * Hv),
                                        jnp.float32) * d ** -0.5,
        "conv_w": jax.random.normal(ks[2], (cfg.gdn_conv, C),
                                    jnp.float32) * cfg.gdn_conv ** -0.5,
        "A_log": jnp.log(A),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "norm": jnp.ones((Dv,), jnp.float32),
        "out_proj": jax.random.normal(ks[5], (Hv * Dv, d),
                                      jnp.float32) * (Hv * Dv) ** -0.5,
    }


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _step(S, q, k, v, g, beta):
    """One position of every (row, head): S (B, H, Dk, Dv); q, k (B, H, Dk);
    v (B, H, Dv); g, beta (B, H).  Elementwise products and sums, so the
    float32 contractions never take a reduced-precision matrix pass."""
    S = S * jnp.exp(g)[..., None, None]
    kv = jnp.sum(S * k[..., :, None], axis=-2)
    delta = (v - kv) * beta[..., None]
    S = S + k[..., :, None] * delta[..., None, :]
    return S, jnp.sum(S * q[..., :, None], axis=-2)


def apply_gdn(p, x, cfg: ArchConfig, *, cache=None, collect: bool = False):
    """x: (B, S, d) -> ((B, S, d), new cache or None).  ``cache`` (decode):
    {"conv", "state"} of the rows; ``collect`` (prefill) returns the cache
    the prompt leaves."""
    from repro.kernels.gdn.ops import gdn_decode
    B, S, d = x.shape
    Hk, Hv, Dk, Dv, C = dims(cfg)
    r = Hv // Hk
    cd, f32 = x.dtype, jnp.float32
    qkvz = (x @ p["in_proj_qkvz"].astype(cd)).reshape(
        B, S, Hk, 2 * Dk + 2 * r * Dv)
    ba = (x @ p["in_proj_ba"].astype(cd)).reshape(B, S, Hk, 2 * r)
    q, k = qkvz[..., :Dk], qkvz[..., Dk:2 * Dk]
    v = qkvz[..., 2 * Dk:2 * Dk + r * Dv]
    z = qkvz[..., 2 * Dk + r * Dv:].reshape(B, S, Hv, Dv)
    b, a = ba[..., :r].reshape(B, S, Hv), ba[..., r:].reshape(B, S, Hv)
    mixed = jnp.concatenate([q.reshape(B, S, Hk * Dk),
                             k.reshape(B, S, Hk * Dk),
                             v.reshape(B, S, Hv * Dv)], axis=-1)

    # causal depthwise conv over the q || k || v channels, carried in decode
    W = cfg.gdn_conv
    prev = (cache["conv"].astype(cd) if cache is not None
            else jnp.zeros((B, W - 1, C), cd))
    full = jnp.concatenate([prev, mixed], axis=1)          # (B, W-1+S, C)
    w = p["conv_w"]
    conv = sum(full[:, i:i + S].astype(f32) * w[i] for i in range(W))
    conv = jax.nn.silu(conv)
    q = conv[..., :Hk * Dk].reshape(B, S, Hk, Dk)
    k = conv[..., Hk * Dk:2 * Hk * Dk].reshape(B, S, Hk, Dk)
    v = conv[..., 2 * Hk * Dk:].reshape(B, S, Hv, Dv)

    beta = jax.nn.sigmoid(b.astype(f32))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a.astype(f32) + p["dt_bias"])
    q = jnp.repeat(_l2norm(q), r, axis=2) * Dk ** -0.5
    k = jnp.repeat(_l2norm(k), r, axis=2)

    if cache is not None:
        state = cache["state"]
    else:
        state = jnp.zeros((B, Hv, Dk, Dv), f32)
    if cache is not None and S == 1:
        o, state = gdn_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], state)
        o = o[:, None]                                     # (B, 1, Hv, Dv)
    else:
        def body(S_, inp):
            return _step(S_, *inp)
        xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
        state, o = jax.lax.scan(body, state.astype(f32), xs)
        o = jnp.moveaxis(o, 0, 1)                          # (B, S, Hv, Dv)

    # gated RMSNorm per head: norm(o) * w * silu(z), float32
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    o = o * p["norm"] * jax.nn.silu(z.astype(f32))
    out = o.reshape(B, S, Hv * Dv).astype(cd) @ p["out_proj"].astype(cd)
    if cache is None and not collect:
        return out, None
    return out, {"conv": full[:, S:], "state": state}
