"""Model assembly: scanned superblock stacks, train forward, KV-cache decode.

The stack is ``block_unit * n_repeats`` (+ optional prologue layers). Per-slot
params are stacked along the repeat axis and the repeat loop is a
``jax.lax.scan`` with per-step remat -- one superblock of HLO regardless of
depth, which keeps 96-layer/340B dry-run compiles tractable and bounds
activation memory.

Caches: per-slot stacked pytrees; decode scans (params, cache) pairs and
emits updated cache slices. Attention caches for ``attn_local`` layers are
ring buffers bounded by the window (what makes gemma-3 long_500k decodable).
A hybrid stack keeps two kinds of per-row state side by side: ``gdn+moe``
layers a conv tail and a float32 recurrent state, attention layers a KV
cache; both are indexed by batch row.

Serving runs :func:`prefill` and :func:`decode_step` as whole-stack programs,
MoE layers included: the model has one execution path.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.masks import NEG_INF
from repro.core.precision import QuantTensor, policy as precision_policy
from repro.models.config import ArchConfig
from repro.models import layers as L
from repro.models import gdn, mamba2, moe, rwkv6

Params = Dict[str, Any]

ATTN_KINDS = ("attn", "attn_local", "attn_global", "attn+moe", "shared_attn")


# ---------------------------------------------------------------- init ------

def init_block(key, kind: str, cfg: ArchConfig) -> Params:
    if kind in ("attn", "attn_local", "attn_global", "attn+moe", "shared_attn"):
        k1, k2 = jax.random.split(key)
        p = {"ln1": L.init_rmsnorm(cfg.d_model),
             "attn": L.init_attention(k1, cfg),
             "ln2": L.init_rmsnorm(cfg.d_model)}
        if kind == "attn+moe":
            p["ffn"] = moe.init_moe(k2, cfg)
        else:
            p["ffn"] = L.init_mlp(k2, cfg)
        return p
    if kind == "gdn+moe":
        k1, k2 = jax.random.split(key)
        return {"ln1": L.init_rmsnorm(cfg.d_model),
                "mixer": gdn.init_gdn(k1, cfg),
                "ln2": L.init_rmsnorm(cfg.d_model),
                "ffn": moe.init_moe(k2, cfg)}
    if kind == "mamba":
        return {"ln": L.init_rmsnorm(cfg.d_model),
                "mixer": mamba2.init_mamba(key, cfg)}
    if kind == "rwkv":
        return {"ln1": L.init_rmsnorm(cfg.d_model),
                "ln2": L.init_rmsnorm(cfg.d_model),
                "mixer": rwkv6.init_rwkv(key, cfg)}
    raise ValueError(kind)


def init_params(key, cfg: ArchConfig) -> Params:
    keys = jax.random.split(key, 8)
    d, V = cfg.d_model, cfg.padded_vocab
    p: Params = {
        "embed": jax.random.normal(keys[0], (V, d), jnp.float32) * (d ** -0.5),
        "final_norm": L.init_rmsnorm(d),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = jax.random.normal(keys[1], (d, V), jnp.float32) * (d ** -0.5)

    # stacked superblock params: one vmapped init per slot
    slot_params = []
    for slot, kind in enumerate(cfg.block_unit):
        slot_keys = jax.random.split(jax.random.fold_in(keys[2], slot), cfg.n_repeats)
        slot_params.append(jax.vmap(lambda k: init_block(k, kind, cfg))(slot_keys))
    p["blocks"] = tuple(slot_params)

    if cfg.shared_attn_every:
        p["shared_attn"] = init_block(keys[3], "shared_attn", cfg)
    if getattr(cfg, "n_prologue", 0):
        pro_keys = jax.random.split(keys[4], cfg.n_prologue)
        p["prologue"] = jax.vmap(
            lambda k: init_block(k, cfg.block_unit[0], cfg))(pro_keys)
    return p


# -------------------------------------------------------- served weights ----

# Weight leaves whose every use in ``prefill`` and ``decode_step`` casts them
# to the compute dtype: the matrices and biases of attention, of the dense,
# shared and held-expert MLPs and of the Gated DeltaNet mixer, the shared
# expert's gate, and the embedding tables.
COMPUTE_LEAVES = frozenset({
    "wq", "wk", "wv", "wo", "bq", "bk", "bv", "w_gate", "w_up", "w_down",
    "in_proj_qkvz", "in_proj_ba", "out_proj", "shared_gate", "embed",
    "unembed"})
# Weight leaves used in float32 whatever the policy: norm scales (rmsnorm
# multiplies in f32), the MoE router (routing runs in f32), and the Gated
# DeltaNet decays, conv taps and output-norm weights.
WIDE_LEAVES = frozenset({"scale", "router", "A_log", "dt_bias", "conv_w",
                         "norm"})


def compute_params(params: Params, cfg: ArchConfig) -> Params:
    """``params`` with every leaf named in :data:`COMPUTE_LEAVES` held in
    the policy's compute dtype, for a driver that passes the tree to a
    compiled step on every call: the step then reads the narrow copy
    instead of converting the wide one each time.  The values are the ones
    each use's ``.astype`` gives, so every logit is bit-identical.  All
    other leaves come back as they are: :data:`WIDE_LEAVES`, quantized
    expert weights (``QuantTensor``), the mamba and RWKV mixers' own
    weights, and every leaf already in the compute dtype (all of them
    under an f32 policy)."""
    cd = precision_policy(cfg.policy).compute_dtype

    def cast(path, a):
        if (isinstance(a, QuantTensor) or a.dtype == cd
                or getattr(path[-1], "key", None) not in COMPUTE_LEAVES):
            return a
        return a.astype(cd)

    return jax.tree_util.tree_map_with_path(
        cast, params, is_leaf=lambda a: isinstance(a, QuantTensor))


# --------------------------------------------------------------- blocks -----

def _window_for(kind: str, cfg: ArchConfig) -> Optional[int]:
    return cfg.local_window if kind == "attn_local" else None


def apply_block(kind: str, p: Params, x, cfg: ArchConfig, *, impl="chunked",
                cache=None, pos=None, collect_kv: int = 0,
                kv_quant: Optional[str] = None, attn_mask=None):
    """One sub-layer. Returns (x, new_cache). ``collect_kv`` > 0 makes the
    prefill path emit a decode cache of that capacity.
    ``kv_quant`` (prefill only) collects full-context attention caches as
    per-position narrow values + f32 scales (see ``layers.apply_attention``);
    decode detects a quantized cache by its scale leaves, no flag needed.
    ``attn_mask`` (an ``AttnMaskSpec``, prefill only) routes attention
    through the block-sparse stream walk."""
    if kind in ATTN_KINDS:
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        attn_cache = cache.get("attn") if cache else None
        a, new_attn = L.apply_attention(
            p["attn"], h, cfg, window=_window_for(kind, cfg), impl=impl,
            cache=attn_cache, cache_len=pos, collect_kv=collect_kv,
            kv_quant=kv_quant, attn_mask=attn_mask)
        x = x + a
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        moe_counts = None
        if kind == "attn+moe":
            # thread the routing occupancy (prefix-stable slots): decode
            # passes the cached per-(row, expert) counts + absolute position
            f, moe_counts = moe.apply_moe(
                p["ffn"], h, cfg, counts=cache.get("moe") if cache else None,
                pos=pos)
        else:
            f = L.apply_mlp(p["ffn"], h, cfg)
        x = x + f
        if new_attn is None:
            return x, None
        new_cache = {"attn": new_attn}
        if kind == "attn+moe":
            new_cache["moe"] = moe_counts
        return x, new_cache
    if kind == "gdn+moe":
        if not cfg.moe_dropless:
            raise ValueError(f"{cfg.name}: gdn+moe layers route dropless "
                             "top-k only (top_k > 1)")
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, new_g = gdn.apply_gdn(p["mixer"], h, cfg,
                                 cache=cache.get("gdn") if cache else None,
                                 collect=bool(collect_kv))
        x = x + a
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        f, moe_state = moe.apply_moe(p["ffn"], h, cfg, pos=pos)
        x = x + f
        if new_g is None:
            return x, None
        return x, {"gdn": new_g, "moe": moe_state}
    if kind == "mamba":
        h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
        m, new_c = mamba2.apply_mamba(p["mixer"], h, cfg, cache=cache,
                                      collect=bool(collect_kv))
        return x + m, new_c
    if kind == "rwkv":
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        t_cache = ({"shift_t": cache["shift_t"], "wkv": cache["wkv"]}
                   if cache else None)
        t, new_t = rwkv6.apply_rwkv_time(p["mixer"], h, cfg, cache=t_cache,
                                         collect=bool(collect_kv))
        x = x + t
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        c_cache = {"shift_c": cache["shift_c"]} if cache else None
        c, new_c = rwkv6.apply_rwkv_channel(p["mixer"], h, cfg, cache=c_cache,
                                            collect=bool(collect_kv))
        x = x + c
        new = None if new_t is None else {**new_t, **(new_c or {})}
        return x, new
    raise ValueError(kind)


def _superblock(params_slots, x, cfg: ArchConfig, *, impl, shared_p,
                step_idx, caches_slots=None, pos=None):
    """Apply one superblock (all slots) + optional shared attention."""
    from repro.parallel import context as pctx
    from repro.parallel.sharding import constrain
    new_caches = []
    for slot, kind in enumerate(cfg.block_unit):
        c = caches_slots[slot] if caches_slots is not None else None
        x, nc = apply_block(kind, params_slots[slot], x, cfg, impl=impl,
                            cache=c, pos=pos)
        if pctx.ACT_SPEC is not None:
            # re-anchor the residual layout after every block: keeps the TP
            # row-parallel reduction a reduce-scatter (not a full all-reduce)
            x = constrain(x, pctx.ACT_SPEC)
        new_caches.append(nc)
    if cfg.shared_attn_every:
        fire = (step_idx % cfg.shared_attn_every) == (cfg.shared_attn_every - 1)
        x = jax.lax.cond(
            fire,
            lambda x: apply_block("shared_attn", shared_p, x, cfg, impl=impl)[0],
            lambda x: x,
            x)
    return x, (tuple(new_caches) if caches_slots is not None else None)


# -------------------------------------------------------------- forward -----

def hidden_forward(params: Params, tokens: jax.Array, cfg: ArchConfig, *,
                   embeddings: Optional[jax.Array] = None,
                   impl: str = "chunked", remat: bool = True) -> jax.Array:
    """Backbone forward: embeddings -> scanned superblocks -> final norm.
    Returns the normed hidden states (B, S_total, d) in compute dtype."""
    from repro.parallel import context as pctx
    from repro.parallel.sharding import constrain
    pol = precision_policy(cfg.policy)
    cd = pol.compute_dtype
    x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
    if embeddings is not None:
        x = jnp.concatenate([embeddings.astype(cd), x], axis=1)
    if pctx.ACT_SPEC is not None:
        x = constrain(x, pctx.ACT_SPEC)

    if "prologue" in params:
        def pro_body(x, p_slice):
            y, _ = apply_block(cfg.block_unit[0], p_slice, x, cfg, impl=impl)
            return y, None
        x, _ = jax.lax.scan(pro_body, x, params["prologue"])

    shared_p = params.get("shared_attn")

    def body(x, inp):
        p_slots, step_idx = inp
        y, _ = _superblock(p_slots, x, cfg, impl=impl, shared_p=shared_p,
                           step_idx=step_idx)
        if pctx.ACT_SPEC is not None:
            y = constrain(y, pctx.ACT_SPEC)
        return y, None

    if remat:
        body = jax.checkpoint(body)
    steps = jnp.arange(cfg.n_repeats)
    x, _ = jax.lax.scan(body, x, (params["blocks"], steps))
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def forward(params: Params, tokens: jax.Array, cfg: ArchConfig, *,
            embeddings: Optional[jax.Array] = None, impl: str = "chunked",
            remat: bool = True) -> jax.Array:
    """Train/prefill forward. tokens: (B, S_text) int32; optional frontend
    ``embeddings``: (B, S_front, d) prepended (vlm/audio stubs). Returns
    logits (B, S_total, V) in f32."""
    x = hidden_forward(params, tokens, cfg, embeddings=embeddings, impl=impl,
                       remat=remat)
    cd = x.dtype
    unemb = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return (x @ unemb.astype(cd)).astype(jnp.float32)


def loss_fn(params: Params, tokens: jax.Array, cfg: ArchConfig, *,
            embeddings: Optional[jax.Array] = None, impl: str = "chunked",
            seq_chunk: Optional[int] = None):
    """Next-token cross-entropy over the token region.

    ``seq_chunk``: compute logits + CE in sequence chunks under remat so the
    (B, S, V) logits tensor is never materialized (essential for 256k-vocab
    archs at 1M tokens/step)."""
    from repro.parallel import context as pctx
    from repro.parallel.sharding import constrain
    h = hidden_forward(params, tokens, cfg, embeddings=embeddings, impl=impl)
    if embeddings is not None:
        h = h[:, embeddings.shape[1]:]
    h = h[:, :-1]
    tgt = tokens[:, 1:]
    cd = h.dtype
    unemb = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    unemb = unemb.astype(cd)

    def ce(h_blk, tgt_blk):
        logits = h_blk @ unemb
        if pctx.LOGIT_SPEC is not None:
            logits = constrain(logits, pctx.LOGIT_SPEC)
        logits = logits.astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:  # mask pad ids out of the CE
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, NEG_INF, logits)
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(lp, tgt_blk[..., None], axis=-1)[..., 0]

    Sm1 = h.shape[1]
    if seq_chunk is None or seq_chunk >= Sm1:
        return ce(h, tgt).mean()
    n = Sm1 // seq_chunk
    main, tail = h[:, : n * seq_chunk], h[:, n * seq_chunk:]
    tgt_main, tgt_tail = tgt[:, : n * seq_chunk], tgt[:, n * seq_chunk:]
    hc = main.reshape(h.shape[0], n, seq_chunk, -1).transpose(1, 0, 2, 3)
    tc = tgt_main.reshape(tgt.shape[0], n, seq_chunk).transpose(1, 0, 2)

    def body(acc, inp):
        hb, tb = inp
        return acc + ce(hb, tb).sum(), None

    body = jax.checkpoint(body)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, tc))
    if tail.shape[1]:
        total = total + ce(tail, tgt_tail).sum()
    return total / (h.shape[0] * Sm1)


def _cache_to_dtype(cache, cd, cache_dtype):
    """Convert compute-dtype cache leaves to the decode cache dtype,
    leaving quantization scale leaves (``k_scale``/``v_scale``) and the
    Gated DeltaNet ``state`` untouched -- they are f32 by contract even
    when the compute dtype is f32."""
    skip = ("k_scale", "v_scale", "state")

    def conv(path, a):
        if path and getattr(path[-1], "key", None) in skip:
            return a
        return a.astype(cache_dtype) if a.dtype == cd else a

    return jax.tree_util.tree_map_with_path(conv, cache)


def prefill(params: Params, tokens: jax.Array, cfg: ArchConfig, *,
            max_seq: int, embeddings: Optional[jax.Array] = None,
            impl: str = "chunked", cache_dtype=jnp.bfloat16,
            kv_quant: Optional[str] = None, attn_mask=None):
    """Serving prefill: forward over the prompt, emitting (last_logits,
    decode cache filled to ``tokens`` length, next position).  ``kv_quant``
    stores full-context KV caches as per-position narrow values + f32
    scales (local ring buffers stay wide).  ``attn_mask`` (AttnMaskSpec)
    routes attention through the block-sparse stream walk."""
    pol = precision_policy(cfg.policy)
    cd = pol.compute_dtype
    x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
    if embeddings is not None:
        x = jnp.concatenate([embeddings.astype(cd), x], axis=1)
    S_total = x.shape[1]
    shared_p = params.get("shared_attn")
    cache: Dict[str, Any] = {}

    if "prologue" in params:
        def pro_body(x, p_slice):
            y, c = apply_block(cfg.block_unit[0], p_slice, x, cfg, impl=impl,
                               collect_kv=max_seq, kv_quant=kv_quant,
                               attn_mask=attn_mask)
            return y, c
        x, pro_cache = jax.lax.scan(pro_body, x, params["prologue"])
        cache["prologue"] = pro_cache

    def body(x, inp):
        p_slots, step_idx = inp
        slot_caches = []
        y = x
        for slot, kind in enumerate(cfg.block_unit):
            y, c = apply_block(kind, p_slots[slot], y, cfg, impl=impl,
                               collect_kv=max_seq, kv_quant=kv_quant,
                               attn_mask=attn_mask)
            slot_caches.append(c)
        if cfg.shared_attn_every:
            fire = (step_idx % cfg.shared_attn_every) == (cfg.shared_attn_every - 1)
            y2, c2 = apply_block("shared_attn", shared_p, y, cfg, impl=impl,
                                 collect_kv=max_seq, kv_quant=kv_quant,
                                 attn_mask=attn_mask)
            y = jnp.where(fire, y2, y)
            slot_caches.append(c2)
        return y, tuple(slot_caches)

    steps = jnp.arange(cfg.n_repeats)
    x, slot_caches = jax.lax.scan(body, x, (params["blocks"], steps))
    cache["slots"] = slot_caches

    x_last = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    unemb = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = (x_last @ unemb.astype(cd)).astype(jnp.float32)
    # KV caches collected in compute dtype; convert to the decode cache dtype
    cache = _cache_to_dtype(cache, cd, cache_dtype)
    return logits, cache, jnp.asarray(S_total, jnp.int32)


# --------------------------------------------------------------- decode -----

def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, kv_quant: Optional[str] = None) -> Params:
    """Stacked decode caches, one entry per slot (+ shared-attn slot).

    Every leaf carries the batch at dim 1 ((n_repeats, B, ...)), and all
    per-request decode state -- attention KV, MoE routing occupancy
    ``counts[b, e]``, SSM/RWKV recurrent state -- is indexed by batch row.
    Batch rows are therefore independent *request slots*: a continuous-
    batching scheduler (``launch.serve.ServeScheduler``) evicts a finished
    sequence and admits a new one by scattering a fresh single-request
    prefill cache into that row, with zero effect on its neighbours."""
    d = cfg.d_model
    hd, Hkv = cfg.hd, cfg.n_kv_heads

    def attn_cache(window):
        Lc = min(max_seq, window) if window else max_seq
        shp = (cfg.n_repeats, batch, Hkv, Lc, hd)
        if kv_quant is not None and not window:
            # Quantized full-context cache: narrow values + per-position
            # f32 scales (scale 1.0 = the all-zero convention of
            # precision.quantize_rows).  Local ring buffers stay wide.
            from repro.core import precision
            qdt = precision.QUANT_DTYPES[kv_quant]
            return {"attn": {
                "k": jnp.zeros(shp, qdt),
                "k_scale": jnp.ones(shp[:-1], jnp.float32),
                "v": jnp.zeros(shp, qdt),
                "v_scale": jnp.ones(shp[:-1], jnp.float32)}}
        return {"attn": {
            "k": jnp.zeros(shp, dtype),
            "v": jnp.zeros(shp, dtype)}}

    def mamba_cache():
        d_in = cfg.ssm_expand * d
        nh = d_in // cfg.ssm_head_dim
        conv_ch = d_in + 2 * cfg.ssm_state
        return {"conv": jnp.zeros((cfg.n_repeats, batch, cfg.ssm_conv - 1, conv_ch), dtype),
                "ssm": jnp.zeros((cfg.n_repeats, batch, nh, cfg.ssm_head_dim,
                                  cfg.ssm_state), jnp.float32)}

    def rwkv_cache():
        nh = d // rwkv6.HEAD_DIM
        return {"wkv": jnp.zeros((cfg.n_repeats, batch, nh, rwkv6.HEAD_DIM,
                                  rwkv6.HEAD_DIM), jnp.float32),
                "shift_t": jnp.zeros((cfg.n_repeats, batch, 1, d), dtype),
                "shift_c": jnp.zeros((cfg.n_repeats, batch, 1, d), dtype)}

    def gdn_cache():
        _, Hv, Dk, Dv, C = gdn.dims(cfg)
        return {"gdn": {
            "conv": jnp.zeros((cfg.n_repeats, batch, cfg.gdn_conv - 1, C),
                              dtype),
            "state": jnp.zeros((cfg.n_repeats, batch, Hv, Dk, Dv),
                               jnp.float32)}}

    def moe_state():
        # top-1: per-(row, expert) occupancy counts make decode slot
        # assignment prefix-stable; dropless: each row's (token, held
        # expert) pairs of the last call (see models.moe)
        shp = ((cfg.n_repeats, batch) if cfg.moe_dropless
               else (cfg.n_repeats, batch, cfg.n_experts))
        return jnp.zeros(shp, jnp.int32)

    def slot_cache(kind, n):
        if kind == "attn+moe":
            c = attn_cache(None)
            c["moe"] = moe_state()
        elif kind == "gdn+moe":
            c = gdn_cache()
            c["moe"] = moe_state()
        elif kind in ("attn", "attn_global", "shared_attn"):
            c = attn_cache(None)
        elif kind == "attn_local":
            c = attn_cache(cfg.local_window)
        elif kind == "mamba":
            c = mamba_cache()
        elif kind == "rwkv":
            c = rwkv_cache()
        else:
            raise ValueError(kind)
        if n != cfg.n_repeats:  # re-stack with a different leading dim
            c = jax.tree.map(lambda a: jnp.zeros((n,) + a.shape[1:], a.dtype), c)
        return c

    slots = [slot_cache(kind, cfg.n_repeats) for kind in cfg.block_unit]
    if cfg.shared_attn_every:
        slots.append(slot_cache("shared_attn", cfg.n_repeats))
    out = {"slots": tuple(slots)}
    if cfg.n_prologue:
        out["prologue"] = slot_cache(cfg.block_unit[0], cfg.n_prologue)
    return out


def _decode_block_attn(kind, p, x, cfg, cache, pos, dtype):
    """Attention decode with ring-buffer handling for local layers.

    ``pos`` is an int32 scalar (whole-batch decode) or a ``(B,)`` vector of
    per-row positions (continuous batching); both paths write the same
    cache slots and mask the same tail per row."""
    window = _window_for(kind, cfg)
    kc = cache["attn"]["k"]
    Lc = kc.shape[2]
    if window and Lc == window:
        # ring buffer: write slot = pos % window; all filled slots visible
        pos_a = jnp.asarray(pos)
        slot = pos_a % window
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        if pos_a.ndim:  # per-row ring slots (continuous batching)
            slot = slot.reshape(-1).astype(jnp.int32)
            q, k1, v1, gate = L._qkv_gate(p["attn"], h, cfg,
                                          pos_a.reshape(-1)[:, None, None])
            b_idx = jnp.arange(x.shape[0])
            knew = kc.at[b_idx, :, slot].set(k1[:, :, 0].astype(kc.dtype))
            vnew = cache["attn"]["v"].at[b_idx, :, slot].set(
                v1[:, :, 0].astype(kc.dtype))
        else:
            q, k1, v1, gate = L._qkv_gate(p["attn"], h, cfg,
                                          jnp.full((1,), pos_a))
            knew = jax.lax.dynamic_update_slice_in_dim(
                kc, k1.astype(kc.dtype), slot, axis=2)
            vnew = jax.lax.dynamic_update_slice_in_dim(
                cache["attn"]["v"], v1.astype(kc.dtype), slot, axis=2)
        from repro.kernels.flash_attention.ops import decode_attention
        a = decode_attention(q, knew, vnew,
                             kv_len=jnp.minimum(pos_a + 1, window))
        a = a.transpose(0, 2, 1, 3).reshape(x.shape[0], 1, cfg.n_heads * cfg.hd)
        if gate is not None:
            a = a * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(a.dtype)
        x = x + a @ p["attn"]["wo"].astype(a.dtype)
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        # ring buffers exist only for attn_local layers, which are never MoE
        f = L.apply_mlp(p["ffn"], h, cfg)
        return x + f, {"attn": {"k": knew, "v": vnew}}
    return apply_block(kind, p, x, cfg, cache=cache, pos=pos)


def blank_cache_row(cache, row: int):
    """Reset one batch row of a stacked decode cache to its freshly
    initialised state: zeros everywhere except quantization scale leaves
    (``k_scale``/``v_scale``), which reset to 1.0 -- the all-zero
    convention of ``precision.quantize_rows``, matching ``init_cache``.

    The eviction half of the slot contract in :func:`init_cache`: a
    scheduler that fails a poisoned request scatter-blanks its row so
    stale NaN/Inf state cannot leak into a later prefill-refill, with zero
    effect on neighbouring rows."""

    def blank(path, a):
        if a.ndim < 2:
            return a
        fill = (jnp.ones if path and getattr(path[-1], "key", None)
                in ("k_scale", "v_scale") else jnp.zeros)
        return a.at[:, row].set(fill(a.shape[2:], a.dtype))

    return jax.tree_util.tree_map_with_path(blank, cache)


def moe_held_pairs(cfg: ArchConfig, cache) -> Optional[jax.Array]:
    """The (token, held expert) pairs the call that made ``cache``
    computed, summed over layers and rows (int32 scalar): what the dropless
    expert share records in its ``moe`` leaves.  None for configurations
    routed otherwise."""
    if not cfg.moe_dropless:
        return None
    leaves = [c["moe"] for c in cache["slots"]
              if isinstance(c, dict) and "moe" in c]
    if "prologue" in cache and "moe" in cache["prologue"]:
        leaves.append(cache["prologue"]["moe"])
    return sum(jnp.sum(a, dtype=jnp.int32) for a in leaves)


def decode_step(params: Params, cfg: ArchConfig, cache, pos, tokens_1,
                dtype=jnp.bfloat16) -> Tuple[jax.Array, Any]:
    """One-token decode. tokens_1: (B, 1) int32; pos: () int32 current fill,
    or a (B,) int32 vector of per-row fills (continuous batching -- every
    batch row decodes at its own position, bit-identical per row to the
    scalar path at that position).
    Returns (logits (B, 1, V) f32, new_cache)."""
    pol = precision_policy(cfg.policy)
    cd = pol.compute_dtype
    x = jnp.take(params["embed"], tokens_1, axis=0).astype(cd)
    shared_p = params.get("shared_attn")
    new_cache = dict(cache)

    if "prologue" in params:
        def pro_body(x, inp):
            p_slice, c_slice = inp
            y, nc = apply_block(cfg.block_unit[0], p_slice, x, cfg,
                                cache=c_slice, pos=pos)
            return y, nc
        x, pro_cache = jax.lax.scan(
            pro_body, x, (params["prologue"], cache["prologue"]))
        new_cache["prologue"] = pro_cache

    def body(x, inp):
        p_slots, c_slots, step_idx = inp
        new_caches = []
        y = x
        for slot, kind in enumerate(cfg.block_unit):
            c = c_slots[slot]
            if kind in ATTN_KINDS:
                y, nc = _decode_block_attn(kind, p_slots[slot], y, cfg, c, pos, dtype)
            else:
                y, nc = apply_block(kind, p_slots[slot], y, cfg, cache=c, pos=pos)
            new_caches.append(nc)
        if cfg.shared_attn_every:
            fire = (step_idx % cfg.shared_attn_every) == (cfg.shared_attn_every - 1)
            c = c_slots[-1]
            y2, nc = _decode_block_attn("shared_attn", shared_p, y, cfg, c, pos, dtype)
            y = jnp.where(fire, y2, y)
            nc = jax.tree.map(lambda new, old: jnp.where(fire, new, old), nc, c)
            new_caches.append(nc)
        return y, tuple(new_caches)

    steps = jnp.arange(cfg.n_repeats)
    x, slot_caches = jax.lax.scan(
        body, x, (params["blocks"], cache["slots"], steps))
    new_cache["slots"] = slot_caches
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    unemb = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return (x @ unemb.astype(cd)).astype(jnp.float32), new_cache
