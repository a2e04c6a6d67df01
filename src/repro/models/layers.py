"""Neural-net layer primitives: norms, RoPE, GQA attention, MLPs.

Pure-function style: ``init_*`` builds a param dict, ``apply_*`` consumes it.
Attention has three interchangeable implementations with one contract:

* ``kernel``  -- the Pallas flash kernel (TPU target; interpret-tested on CPU)
* ``chunked`` -- pure-jnp online-softmax over KV chunks: identical memory
                 profile to the kernel (no (S,S) materialization), lowerable on
                 any backend -- this is what the multi-pod dry-run rooflines.
* ``ref``     -- materialized softmax oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.masks import NEG_INF, AttnMaskSpec
from repro.models.config import ArchConfig


# ----------------------------------------------------------------- norms ----

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


# ------------------------------------------------------------------ rope ----

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               fraction: float = 1.0) -> jax.Array:
    """x: (..., S, hd); positions: (S,) or broadcastable.  ``fraction`` < 1
    rotates only the first ``int(hd * fraction)`` dims of each head (their
    two halves, frequencies over that width) and passes the rest through."""
    if fraction != 1.0:
        rd = int(x.shape[-1] * fraction)
        return jnp.concatenate(
            [apply_rope(x[..., :rd], positions, theta),
             x[..., rd:]], axis=-1)
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- attention ----

_FLASH_CVJP_CACHE = {}


def flash_fwd_chunked_bwd(causal: bool, window):
    """Differentiable kernelized attention: the Pallas flash kernel on the
    forward (streaming memory profile), the chunked-jnp VJP on the backward
    (per-chunk remat; the flash backward kernel is future work). This is what
    lets *train* steps run the kernel forward (SPerf-E)."""
    key = (causal, window)
    if key in _FLASH_CVJP_CACHE:
        return _FLASH_CVJP_CACHE[key]

    @jax.custom_vjp
    def f(q, k, v):
        return sharded_flash_attention(q, k, v, causal=causal, window=window)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: chunked_attention(q_, k_, v_, causal=causal,
                                                 window=window), q, k, v)
        return vjp(g)

    f.defvjp(fwd, bwd)
    _FLASH_CVJP_CACHE[key] = f
    return f


def sharded_flash_attention(q, k, v, *, causal=True, window=None):
    """Pallas flash kernel under shard_map: q sequence-sharded over "model",
    batch over the FSDP axes; K/V gathered per shard (the gather SP performs
    anyway). Scores never leave VMEM -- the SPerf-D lever for prefill.

    Inference-only (the kernel has no custom VJP); the train path keeps the
    differentiable chunked formulation.
    """
    from repro.parallel import context as pctx
    from repro.parallel.sharding import FSDP
    from repro.kernels.flash_attention.kernel import flash_attention as _fk
    mesh = pctx.MESH
    if mesh is None:
        from repro.kernels.flash_attention.ops import attention as flash
        return flash(q, k, v, causal=causal, window=window)
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in FSDP if a in mesh.axis_names)
    dp = dp if len(dp) > 1 else dp[0]
    tp = "model"
    S = q.shape[2]
    S_loc = S // mesh.shape[tp]
    from repro.kernels import tuning
    interpret = not tuning.on_tpu()

    def body(qb, kb, vb):
        off = jax.lax.axis_index(tp) * S_loc
        return _fk(qb, kb, vb, causal=causal, window=window, q_offset=off,
                   bq=min(128, S_loc), bk=128, interpret=interpret)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, tp, None), P(dp, None, None, None),
                  P(dp, None, None, None)),
        out_specs=P(dp, None, tp, None),
        check_vma=False)  # pallas_call outputs carry no vma metadata
    return fn(q, k, v)


def init_attention(key, cfg: ArchConfig):
    d, hd, Hq, Hkv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    q_out = Hq * hd * (2 if cfg.attn_output_gate else 1)
    p = {
        "wq": jax.random.normal(k1, (d, q_out), jnp.float32) * s,
        "wk": jax.random.normal(k2, (d, Hkv * hd), jnp.float32) * s,
        "wv": jax.random.normal(k3, (d, Hkv * hd), jnp.float32) * s,
        "wo": jax.random.normal(k4, (Hq * hd, d), jnp.float32) * s,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((Hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((Hkv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def _qkv_gate(p, x, cfg: ArchConfig, positions):
    """(q, k, v, gate): ``gate`` is None, or with ``cfg.attn_output_gate``
    the (B, S, Hq * hd) pre-sigmoid output gate, which the q projection
    emits after each head's query ([query | gate] per head)."""
    B, S, d = x.shape
    hd, Hq, Hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    cd = x.dtype
    q = x @ p["wq"].astype(cd)
    k = x @ p["wk"].astype(cd)
    v = x @ p["wv"].astype(cd)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    gate = None
    if cfg.attn_output_gate:
        qg = q.reshape(B, S, Hq, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:].reshape(B, S, Hq * hd)
    q = q.reshape(B, S, Hq, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v, gate


def chunked_attention(q, k, v, *, causal=True, window=None, chunk=1024):
    """Online-softmax over KV chunks in pure jnp (flash semantics, XLA-fused).

    q: (B, Hq, Sq, hd); k/v: (B, Hkv, Skv, hd).

    Occamy-style multi-precision discipline: operands stream in their narrow
    dtype (bf16) and only the MXU accumulators widen to f32 (the ExSdotp
    pattern) -- no f32 K/V buffers, no materialized GQA head repeat. This
    halves HBM and collective traffic vs. the naive formulation (measured in
    EXPERIMENTS.md SPerf).
    """
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    g = Hq // Hkv
    scale = hd ** -0.5
    chunk = min(chunk, Skv)
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_chunks = (Skv + pad) // chunk
    kc = k.reshape(B, Hkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    # GQA without repeat: group dim g rides along the head dim of q only
    qg = (q * scale).astype(k.dtype).reshape(B, Hkv, g, Sq, hd)
    q_pos = jnp.arange(Sq)[:, None]

    def body(carry, inp):
        m, l, acc, ci = carry
        kb, vb = inp                                      # (B, Hkv, chunk, hd)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb,
                       preferred_element_type=jnp.float32)
        k_pos = ci * chunk + jnp.arange(chunk)[None, :]
        mask = k_pos < Skv
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc, ci + 1), None

    init = (jnp.full((B, Hkv, g, Sq, 1), NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, g, Sq, 1), jnp.float32),
            jnp.zeros((B, Hkv, g, Sq, hd), jnp.float32),
            jnp.asarray(0, jnp.int32))
    # flash backward = recompute: without this, AD stacks per-chunk scores/
    # probs across ALL chunks (n_chunks x (B,H,Sq,chunk) f32 residuals)
    body = jax.checkpoint(body)
    (m, l, acc, _), _ = jax.lax.scan(body, init, (kc, vc))
    out = acc / jnp.where(l == 0, 1.0, l)
    return out.reshape(B, Hq, Sq, hd).astype(q.dtype)


def _masked_prefill_attention(q, k, v, spec: AttnMaskSpec, window):
    """Prefill through the block-sparse stream walk when the AttnMaskSpec
    applies to this layer (sliding-window layers via ``spec.local``,
    full-attention layers via ``spec.pattern``); None -> caller falls back
    to the dense impl dispatch.  Mask construction is host numpy on static
    shapes, so it runs once per trace and the lowered stream becomes a
    compile-time operand (recompiles keyed on pattern signature x bucket).
    """
    from repro.kernels import tuning
    from repro.kernels.flash_attention import ops as fops
    S, D = q.shape[2], q.shape[3]
    pattern = "window" if window is not None else spec.pattern
    bq, bk = spec.bq, spec.bk
    if bq is None or bk is None:
        tbq, tbk = tuning.flash_sparse_tiles(S, S, D, q.dtype,
                                             pattern=pattern)
        bq, bk = bq or tbq, bk or tbk
    mask = spec.build(S, S, layer_window=window, bq=bq, bk=bk)
    if mask is None:
        return None
    return fops.attention(q, k, v, mask=mask, mask_impl=spec.impl,
                          interpret=not tuning.on_tpu())


def apply_attention(p, x, cfg: ArchConfig, *, window=None, positions=None,
                    impl: str = "chunked", cache=None, cache_len=None,
                    collect_kv: int = 0, kv_quant: Optional[str] = None,
                    attn_mask: Optional[AttnMaskSpec] = None):
    """Self-attention (train/prefill) or one-step decode when ``cache`` given.

    cache: dict(k=(B,Hkv,S,hd), v=...) -- updated functionally; ``cache_len``
    is the current fill: an int32 scalar (whole-batch decode, every row at
    the same position) or an int32 ``(B,)`` vector (continuous batching,
    every row at its own position -- the write becomes a per-row scatter and
    RoPE/masking use per-row positions; per row the arithmetic is identical
    to the scalar path at that row's position).
    ``collect_kv``: when > 0 (prefill), also return a fresh KV cache of that
    capacity filled with this call's keys/values (window-truncated for local
    layers).
    ``kv_quant``: narrow dtype name ("fp8_e4m3"/"fp8_e5m2"/"int8") to store
    the collected cache as per-position BlockQuant values (``k``/``v``
    narrow + ``k_scale``/``v_scale`` f32 over head_dim).  Only applies to
    full-context layers (``window is None``) -- local ring buffers stay
    wide.  Decode auto-detects a quantized cache by its ``k_scale`` leaf:
    new keys/values are quantized per position before the scatter and the
    whole cache is dequantized to the query dtype before attention.
    ``attn_mask``: an ``AttnMaskSpec`` routes prefill through the
    block-sparse stream-walk kernel (sliding-window layers and/or an opt-in
    long-context pattern); decode is untouched.
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    if cache is None:
        positions = positions if positions is not None else jnp.arange(S)
        q, k, v, gate = _qkv_gate(p, x, cfg, positions)
        out = None
        if attn_mask is not None:
            out = _masked_prefill_attention(q, k, v, attn_mask, window)
        if out is not None:
            pass
        elif impl == "kernel":
            from repro.kernels.flash_attention.ops import attention as flash
            out = flash(q, k, v, causal=True, window=window)
        elif impl == "kernel_sharded":
            out = flash_fwd_chunked_bwd(True, window)(q, k, v)
        elif impl == "chunked":
            out = chunked_attention(q, k, v, causal=True, window=window)
        else:
            from repro.kernels.flash_attention.ref import attention_ref
            out = attention_ref(q, k, v, causal=True, window=window)
        new_cache = None
        if collect_kv:
            cap = min(collect_kv, window) if window else collect_kv
            if window and S >= window:
                # local-layer ring buffer: keep the last `window` positions,
                # placed at their ring slots (pos % window)
                order = jnp.argsort(positions[-window:] % window)
                kc = jnp.take(k[:, :, -window:], order, axis=2)
                vc = jnp.take(v[:, :, -window:], order, axis=2)
            else:
                pad = cap - S
                kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            if kv_quant is not None and not window:
                from repro.core import precision
                qk, sk = precision.quantize_rows(kc, kv_quant)
                qv, sv = precision.quantize_rows(vc, kv_quant)
                new_cache = {"k": qk, "k_scale": sk, "v": qv, "v_scale": sv}
            else:
                new_cache = {"k": kc, "v": vc}
    else:
        assert S == 1
        quant = "k_scale" in cache
        if quant:
            from repro.core import precision
            qname = precision.quant_name(cache["k"].dtype)
        pos = jnp.asarray(cache_len)
        if pos.ndim:  # per-row fill pointers (continuous batching)
            pos = pos.reshape(-1).astype(jnp.int32)
            q, k1, v1, gate = _qkv_gate(p, x, cfg, pos[:, None, None])
            b_idx = jnp.arange(B)
            if quant:
                qk1, sk1 = precision.quantize_rows(k1[:, :, 0], qname)
                qv1, sv1 = precision.quantize_rows(v1[:, :, 0], qname)
                kc = cache["k"].at[b_idx, :, pos].set(qk1)
                vc = cache["v"].at[b_idx, :, pos].set(qv1)
                ks = cache["k_scale"].at[b_idx, :, pos].set(sk1)
                vs = cache["v_scale"].at[b_idx, :, pos].set(sv1)
            else:
                kc = cache["k"].at[b_idx, :, pos].set(
                    k1[:, :, 0].astype(cache["k"].dtype))
                vc = cache["v"].at[b_idx, :, pos].set(
                    v1[:, :, 0].astype(cache["v"].dtype))
        else:
            pos = pos.reshape(())  # scalar fill pointer
            q, k1, v1, gate = _qkv_gate(p, x, cfg, jnp.full((1,), pos))
            if quant:
                qk1, sk1 = precision.quantize_rows(k1, qname)
                qv1, sv1 = precision.quantize_rows(v1, qname)
                kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], qk1, pos, axis=2)
                vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], qv1, pos, axis=2)
                ks = jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], sk1, pos, axis=2)
                vs = jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], sv1, pos, axis=2)
            else:
                kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k1.astype(cache["k"].dtype), pos, axis=2)
                vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v1.astype(cache["v"].dtype), pos, axis=2)
        from repro.kernels.flash_attention.ops import decode_attention
        if quant:
            out = decode_attention(q, precision.dequantize_rows(kc, ks, q.dtype),
                                   precision.dequantize_rows(vc, vs, q.dtype),
                                   kv_len=pos + 1, window=window)
            new_cache = {"k": kc, "k_scale": ks, "v": vc, "v_scale": vs}
        else:
            out = decode_attention(q, kc, vc, kv_len=pos + 1, window=window)
            new_cache = {"k": kc, "v": vc}
    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.hd)
    if gate is not None:
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
    return out @ p["wo"].astype(out.dtype), new_cache


# ------------------------------------------------------------------- mlp ----

def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    s = d ** -0.5
    if cfg.mlp_type == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {"w_gate": jax.random.normal(k1, (d, ff), jnp.float32) * s,
                "w_up": jax.random.normal(k2, (d, ff), jnp.float32) * s,
                "w_down": jax.random.normal(k3, (ff, d), jnp.float32) * (ff ** -0.5)}
    k1, k2 = jax.random.split(key)
    return {"w_up": jax.random.normal(k1, (d, ff), jnp.float32) * s,
            "w_down": jax.random.normal(k2, (ff, d), jnp.float32) * (ff ** -0.5)}


def apply_mlp(p, x, cfg: ArchConfig):
    cd = x.dtype
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(cd)) * (x @ p["w_up"].astype(cd))
    else:  # squared_relu (Nemotron-4)
        h = jnp.square(jax.nn.relu(x @ p["w_up"].astype(cd)))
    return h @ p["w_down"].astype(cd)
