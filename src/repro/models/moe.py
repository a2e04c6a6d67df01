"""Mixture-of-Experts: prefix-stable routing + pluggable SU dispatch.

This is where the paper's technique is first-class in the LM stack: routing
tokens to experts *is* a sparse-dense product, and the layer is split into
the two stages that framing implies.

Two routing rules, chosen by ``top_k`` (``cfg.moe_dropless``):

* top-1 with a prefix capacity (below; Llama-4 style);
* dropless top-k (:func:`apply_moe_dropless`; Qwen3-Next style): softmax
  over all ``n_experts`` router logits in f32, the top ``top_k``, their
  weights renormalised to sum 1.  A token meets an expert at most once, so
  a per-(row, expert) queue of S slots never drops.  The layer is an
  *expert share*: it holds experts ``[expert_offset, expert_offset +
  experts_held)``, routes over all ``n_experts`` and computes only its own
  experts' part of the result (the shared expert, which every chip computes
  alike, is added in full).

**Routing stage** (:func:`route_tokens`) -- prefix-stable by construction.
The slot of a token in its expert's queue is a pure function of the token's
own (batch row, position, expert) history: slots are assigned by cumsum
along the *sequence* dim per (row, expert), offset by an occupancy count
``counts[row, expert]`` carried across calls (the decode cache threads it),
and the keep/drop decision compares the slot against the *prefix* capacity

    C(t) = ceil((t + 1) / E * capacity_factor)

where ``t`` is the token's absolute position.  Because neither the slot nor
the capacity depends on which other rows share the batch or on how many
future tokens follow, a one-token decode step reproduces exactly the slot --
and the drop decision -- the same token gets inside a prefill.  (The old
formulation cumsummed over the flattened in-batch token stream with a
whole-batch capacity, so decode saw a different drop set than prefill;
see ROADMAP PR-2.)  Occupancy counts *all* routed tokens, kept or dropped,
so the queue position is a plain cumsum of the assignment one-hots.

**Dispatch stage** -- ``moe_dispatch="gather" | "bcsr"`` (ArchConfig field,
overridable via ``repro.parallel.context.MOE_DISPATCH`` or the ``dispatch=``
argument):

* ``"gather"`` -- SU indirection: the inverse index stream gathers token
  rows into dense (E, B, C, d) capacity tiles (``jnp.take_along_axis``).
* ``"bcsr"``   -- the dispatch matrix itself is materialized as a
  :class:`~repro.core.formats.BatchedBCSR` (one shared index stream, one
  0/1 block set per batch row) and run through
  ``repro.kernels.engine.shard_spmm_batched`` -- the SpMM Pallas kernel on
  the device mesh.  Under tracing (inside ``lax.scan``/``jit``) the block
  stream falls back to the full grid (data-dependent sparsity cannot change
  static shapes); eagerly it compacts to the union nonzero-block pattern.
  Tile sizes come from ``kernels.tuning`` (op ``"moe_dispatch"``).

Both backends produce bit-identical dispatch buffers (the BCSR path
multiplies by exact 0/1 blocks with f32 accumulation), so the backends are
interchangeable mid-deployment.  The grouped expert GEMM consumes dense
(E, B*C, d) tiles and combine gathers results back by the same index stream.

Expert-parallel: the leading E dim of expert weights shards over the
"model" axis; the gather/scatter becomes an all-to-all under pjit.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import QuantTensor, quantize_tensor
from repro.models.config import ArchConfig
from repro.models.layers import init_mlp, apply_mlp


def init_moe(key, cfg: ArchConfig):
    """Router (d, n_experts) over every expert; expert weights only for the
    ``n_held`` held here, at ``ff_expert`` wide."""
    d, ff, E = cfg.d_model, cfg.ff_expert, cfg.n_held
    k_r, k_e, k_s = jax.random.split(key, 3)
    s = d ** -0.5
    n_w = 3 if cfg.mlp_type == "swiglu" else 2
    keys = jax.random.split(k_e, n_w)
    if cfg.mlp_type == "swiglu":
        experts = {
            "w_gate": jax.random.normal(keys[0], (E, d, ff), jnp.float32) * s,
            "w_up": jax.random.normal(keys[1], (E, d, ff), jnp.float32) * s,
            "w_down": jax.random.normal(keys[2], (E, ff, d), jnp.float32) * (ff ** -0.5),
        }
    else:
        experts = {
            "w_up": jax.random.normal(keys[0], (E, d, ff), jnp.float32) * s,
            "w_down": jax.random.normal(keys[1], (E, ff, d), jnp.float32) * (ff ** -0.5),
        }
    p = {"router": jax.random.normal(k_r, (d, cfg.n_experts),
                                     jnp.float32) * s,
         "experts": experts}
    if cfg.moe_shared_expert:
        p["shared"] = init_mlp(k_s, cfg, d_ff=cfg.ff_shared)
        if cfg.moe_shared_gate:
            p["shared_gate"] = jax.random.normal(
                jax.random.fold_in(k_s, 1), (d, 1), jnp.float32) * s
    return p


def _wcast(w, cd):
    """Weight accessor of the expert GEMMs: dequantize BlockQuant weights
    (narrow values * per-channel f32 scales) or plain-cast wide ones."""
    if isinstance(w, QuantTensor):
        return w.dequantize(cd)
    return w.astype(cd)


def _expert_ffn(experts, xe, mlp_type: str):
    """xe: (E, C, d) -> (E, C, d); batched over the expert dim (EP shards it)."""
    cd = xe.dtype
    if mlp_type == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, _wcast(experts["w_gate"], cd)))
        h = h * jnp.einsum("ecd,edf->ecf", xe, _wcast(experts["w_up"], cd))
    else:
        h = jnp.square(jax.nn.relu(
            jnp.einsum("ecd,edf->ecf", xe, _wcast(experts["w_up"], cd))))
    return jnp.einsum("ecf,efd->ecd", h, _wcast(experts["w_down"], cd))


def quantize_expert_weights(params, dtype, *, rounding: str = "nearest",
                            seed: int = 0):
    """Opt-in BlockQuant of the expert FFN weights (the serving memory hog:
    ``E`` copies of every MLP matrix).

    Each ``experts`` leaf ``(..., E, d_in, d_out)`` becomes a
    :class:`~repro.core.precision.QuantTensor` with one f32 scale per
    (expert, output channel) -- scales over the contraction axis ``-2``, so
    the quantization error of one input channel never leaks across output
    channels.  The negative axis makes the QuantTensor *slice-stable*: a
    repeat-stacked leaf ``(n_repeats, E, d_in, d_out)`` keeps a valid axis
    after ``lax.scan`` strips the leading dim.  Router / shared-expert /
    non-MoE params are untouched, and the QuantTensor leaves flow through
    ``apply_moe`` transparently (pytree); :func:`_wcast` dequantizes at the
    einsum boundary.  Returns a new params dict (input unchanged)."""
    if "experts" not in params:
        raise ValueError(
            f"quantize_expert_weights: params has no 'experts' subtree "
            f"(keys: {sorted(params)})")
    out = dict(params)
    out["experts"] = {
        k: quantize_tensor(w, dtype, axis=-2, rounding=rounding, seed=seed)
        for k, w in params["experts"].items()}
    return out


def quantize_model_experts(params, dtype, *, rounding: str = "nearest",
                           seed: int = 0):
    """Model-level twin of :func:`quantize_expert_weights`: walk the stacked
    block slots (+ prologue) of a full ``model.init_params`` dict and
    quantize every attn+moe slot's expert weights.  Raises if the model has
    no MoE slot at all (a silent no-op would masquerade as a memory win)."""
    def q_slot(slot):
        if isinstance(slot, dict) and isinstance(slot.get("ffn"), dict) \
                and "experts" in slot["ffn"]:
            s = dict(slot)
            s["ffn"] = quantize_expert_weights(slot["ffn"], dtype,
                                               rounding=rounding, seed=seed)
            return s, True
        return slot, False

    out = dict(params)
    hit = False
    if "blocks" in params:
        new_slots = []
        for slot in params["blocks"]:
            s, h = q_slot(slot)
            hit |= h
            new_slots.append(s)
        out["blocks"] = tuple(new_slots)
    if "prologue" in params:
        s, h = q_slot(params["prologue"])
        hit |= h
        out["prologue"] = s
    if not hit:
        raise ValueError(
            "quantize_model_experts: no attn+moe slot with an 'experts' "
            "subtree found in params")
    return out


# ----------------------------------------------------------------- routing --

class Routing(NamedTuple):
    """Per-token routing decision (all leading dims (B, S))."""
    gate: jax.Array        # f32 top-1 router probability
    expert_id: jax.Array   # int32 assigned expert
    slot: jax.Array        # int32 absolute position in the (row, expert) queue
    within: jax.Array      # int32 queue position within THIS call (slot - base)
    keep: jax.Array        # bool  slot < prefix capacity at the token's position
    new_counts: jax.Array  # (B, E) int32 occupancy after this call
    logits: jax.Array      # (B, S, E) f32 router logits (for aux losses)


def prefix_capacity(t, n_experts: int, capacity_factor: float) -> jax.Array:
    """Per-(row, expert) queue capacity after ``t + 1`` tokens:
    ``ceil((t+1)/E * capacity_factor)``.  Traceable in ``t``; decode and
    prefill call it with the same absolute positions, so the keep sets are
    bit-identical (the multiply happens in f32 in both)."""
    t1 = (jnp.asarray(t, jnp.int32) + 1).astype(jnp.float32)
    return jnp.ceil(t1 * np.float32(capacity_factor / n_experts)).astype(jnp.int32)


def dispatch_capacity(S: int, cfg: ArchConfig, pos0=0) -> int:
    """Static capacity of the dispatch buffer for an S-token call starting at
    absolute position ``pos0``.  Kept tokens satisfy ``within < S`` and
    ``within <= slot < C(pos0 + S - 1)``, so the min of the two bounds is a
    safe buffer size; when ``pos0`` is traced (stepwise decode) only the
    S bound is static.  Uses the same f32 arithmetic as
    :func:`prefix_capacity` so the bound can never be under the keep test.
    Traced *and* per-row-vector ``pos0`` (continuous batching) both take
    the S bound -- the capacity must be one static int for the batch."""
    if not isinstance(pos0, (int, np.integer)):
        return max(1, S)
    cap = int(np.ceil(np.float32(pos0 + S)
                      * np.float32(cfg.capacity_factor / cfg.n_experts)))
    return max(1, min(S, cap))


def route_tokens(router: jax.Array, x: jax.Array, cfg: ArchConfig, *,
                 counts: Optional[jax.Array] = None, pos0=0) -> Routing:
    """Top-1 routing with prefix-stable slot assignment (the capacity
    rule; top-k configurations route with :func:`route_topk` instead).

    x: (B, S, d); ``counts``: (B, E) int32 occupancy carried from previous
    calls on the same rows (None = fresh sequence); ``pos0``: absolute
    position of x[:, 0] -- an int / traced scalar shared by the whole
    batch, or a ``(B,)`` vector of per-row positions (continuous batching:
    each request slot sits at its own depth in its own sequence).  The
    decision for token (b, s) depends only on row b's tokens at positions
    <= pos0[b] + s, so it is identical to routing that row alone.
    """
    B, S, _ = x.shape
    E = cfg.n_experts
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)   # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_id = jax.lax.top_k(probs, 1)
    gate, expert_id = gate[..., 0], expert_id[..., 0].astype(jnp.int32)

    onehot = jax.nn.one_hot(expert_id, E, dtype=jnp.int32)        # (B, S, E)
    if counts is None:
        counts = jnp.zeros((B, E), jnp.int32)
    # queue position = prior same-(row, expert) tokens, kept OR dropped
    within = ((jnp.cumsum(onehot, axis=1) - onehot) * onehot).sum(-1)
    base = (counts[:, None, :] * onehot).sum(-1)                  # (B, S)
    slot = base + within
    t_abs = (jnp.asarray(pos0, jnp.int32)[..., None]
             + jnp.arange(S, dtype=jnp.int32))       # (S,) or (B, S)
    cap = prefix_capacity(t_abs, E, cfg.capacity_factor)
    keep = slot < (cap if cap.ndim == 2 else cap[None, :])
    new_counts = counts + onehot.sum(axis=1)
    return Routing(gate, expert_id, slot, within, keep, new_counts, logits)


def route_topk(router: jax.Array, x: jax.Array, cfg: ArchConfig):
    """Dropless top-k routing: ``(weights (B, S, k) f32, expert ids (B, S,
    k) int32)``.  Softmax over all ``n_experts`` logits in f32 (the logits
    at HIGHEST precision, so near-ties rank as in float32), the top
    ``top_k``, and the k weights renormalised to sum 1."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    return w / jnp.sum(w, axis=-1, keepdims=True), ids.astype(jnp.int32)


def apply_moe_dropless(p, x, cfg: ArchConfig):
    """The expert share of a dropless top-k layer.  x: (B, S, d) ->
    ((B, S, d), held pairs (B,) int32).

    A token routed to held expert e sits at its own position s in e's
    queue: the dispatch buffer is (E_held, B, S, d), the token's row where
    it is routed and zeros elsewhere, so no capacity can drop it.  The
    output is the held experts' outputs weighted by their renormalised
    router weights, plus the shared expert (gated by ``sigmoid(x @
    shared_gate)`` where the configuration has the gate).  The second
    output counts each row's (token, held expert) pairs: the routed work
    this share did."""
    B, S, d = x.shape
    E, cd = cfg.n_held, x.dtype
    w, ids = route_topk(p["router"], x, cfg)
    eq = ids[..., :, None] == (cfg.expert_offset
                               + jnp.arange(E, dtype=jnp.int32))
    routed = jnp.any(eq, axis=2)                          # (B, S, E)
    comb = jnp.sum(jnp.where(eq, w[..., None], 0.0), axis=2)
    xe = jnp.where(jnp.moveaxis(routed, -1, 0)[..., None], x[None],
                   jnp.zeros((), cd))                     # (E, B, S, d)
    ye = _expert_ffn(p["experts"], xe.reshape(E, B * S, d),
                     cfg.mlp_type).reshape(E, B, S, d)
    out = jnp.sum(ye.astype(jnp.float32)
                  * jnp.moveaxis(comb, -1, 0)[..., None], axis=0)
    if cfg.moe_shared_expert:
        sh = apply_mlp(p["shared"], x, cfg).astype(jnp.float32)
        if cfg.moe_shared_gate:
            sh = sh * jax.nn.sigmoid(
                (x @ p["shared_gate"].astype(cd)).astype(jnp.float32))
        out = out + sh
    return out.astype(cd), jnp.sum(routed, axis=(1, 2), dtype=jnp.int32)


# ---------------------------------------------------------------- dispatch --

def _dispatch_gather(xt: jax.Array, flat_slot: jax.Array, E: int, C: int):
    """SU indirection dispatch: inverse index stream + gather.

    xt: (B, S, d); flat_slot: (B, S) in [0, E*C] (E*C = dropped).
    Returns (E, B, C, d) capacity tiles."""
    B, S, d = xt.shape
    inv = jnp.full((B, E * C + 1), S, jnp.int32)
    inv = inv.at[jnp.arange(B)[:, None], flat_slot].set(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)),
        mode="drop")[:, : E * C]
    xt_pad = jnp.concatenate([xt, jnp.zeros((B, 1, d), xt.dtype)], axis=1)
    xe = jnp.take_along_axis(xt_pad, inv[..., None], axis=1)      # (B, E*C, d)
    return xe.reshape(B, E, C, d).transpose(1, 0, 2, 3)


def _dispatch_grid(S: int, E: int, C: int, bm: int, bk: int):
    """The padded block geometry of the (slot, token) dispatch matrix:
    (M, Mp, Sp, gm, gn).  Single source of truth shared by the traced
    full-grid path and the routed-stream builder -- these two must agree or
    the eager and traced dispatch paths stop being bit-identical."""
    M = E * C
    Mp = -(-M // bm) * bm
    Sp = -(-S // bk) * bk
    return M, Mp, Sp, Mp // bm, Sp // bk


def _dispatch_matrix_tiles(flat_slot: jax.Array, S: int, E: int, C: int,
                           bm: int, bk: int, dtype):
    """(bm, bk)-tiled 0/1 dispatch matrix for the bcsr backends.

    Returns (tiles4 (B, gm, gn, bm, bk), Mp, Sp): the (slot, token) dispatch
    matrix per batch row, zero-padded to block multiples; dropped tokens
    write the slice-off row ``Mp`` so they vanish from every tile."""
    B = flat_slot.shape[0]
    M, Mp, Sp, gm, gn = _dispatch_grid(S, E, C, bm, bk)
    rows = jnp.where(flat_slot < M, flat_slot, Mp)
    disp = jnp.zeros((B, Mp + 1, Sp), dtype)
    disp = disp.at[jnp.arange(B)[:, None], rows,
                   jnp.arange(S, dtype=jnp.int32)[None, :]].set(1)[:, :Mp]
    return disp.reshape(B, gm, bm, gn, bk).transpose(0, 1, 3, 2, 4), Mp, Sp


def _build_routed_stream(flat_slot, S: int, E: int, C: int, bm: int,
                         bk: int, dtype):
    """Compacted dispatch stream straight from *concrete* slots, host-side,
    for the eager bcsr backend: union nonzero-block pattern over the batch,
    every-block-row-appears coverage (kernel contract, zero block at col 0),
    (row, col)-sorted stream.  Cost is O(B*S + nnzb*bm*bk) -- it never
    touches the dense E*C x T grid, only the one (slot, token) entry each
    kept token contributes.

    Returns (BatchedBCSR, nnzb_routed, nnzb_covered): data blocks before
    row coverage, and the covered stream length."""
    from repro.core.formats import BatchedBCSR

    fs = np.asarray(flat_slot)
    B = fs.shape[0]
    M, Mp, Sp, gm, gn = _dispatch_grid(S, E, C, bm, bk)
    if fs.size and (fs.min() < 0 or fs.max() > M):
        # Negative slots would silently wrap through numpy fancy indexing
        # into a *valid-looking* but corrupt stream; out-of-range positives
        # likewise.  A routed slot is in [0, M) or == M (dropped), full stop.
        raise ValueError(
            f"_build_routed_stream: flat_slot out of range "
            f"[{int(fs.min())}, {int(fs.max())}] vs dispatch grid M={M} "
            f"(corrupt routing output -- non-finite logits or a poisoned "
            f"occupancy cache upstream?)")
    b_idx, s_idx = np.nonzero(fs < M)        # kept tokens (dropped = M)
    slots = fs[b_idx, s_idx]
    keys = (slots // bm).astype(np.int64) * gn + s_idx // bk
    coords = np.unique(keys)                  # sorted == (row, col)-sorted
    nnzb_routed = len(coords)
    present = np.zeros(gm, bool)
    present[(coords // gn).astype(np.int32)] = True
    coords = np.union1d(coords,
                        np.nonzero(~present)[0].astype(np.int64) * gn)
    nnzb_covered = len(coords)
    idx = np.searchsorted(coords, keys)
    brows = (coords // gn).astype(np.int32)
    bcols = (coords % gn).astype(np.int32)
    blocks = np.zeros((B, nnzb_covered, bm, bk), np.dtype(dtype))
    blocks[b_idx, idx, slots % bm, s_idx % bk] = 1
    indptr = np.zeros(gm + 1, np.int32)
    np.cumsum(np.bincount(brows, minlength=gm), out=indptr[1:])
    stream = BatchedBCSR(indptr=jnp.asarray(indptr),
                         block_rows=jnp.asarray(brows),
                         block_cols=jnp.asarray(bcols),
                         blocks=jnp.asarray(blocks),
                         shape=(B, Mp, Sp), block=(bm, bk))
    return stream, nnzb_routed, nnzb_covered


def _dispatch_bcsr(xt: jax.Array, flat_slot: jax.Array, E: int, C: int):
    """Dispatch-as-SpMM: per-row 0/1 dispatch matrices as one BatchedBCSR
    (shared index stream) through the sharded SpMM Pallas kernel.

    Eagerly the stream compacts to the union nonzero-block pattern; under
    tracing (the fused serving step) the pattern is the full grid, since
    data-dependent sparsity cannot change static shapes.
    Returns (E, B, C, d), bit-identical to :func:`_dispatch_gather` (0/1
    blocks, f32 accumulate).
    """
    from repro.core.formats import BatchedBCSR
    from repro.kernels import engine, tuning

    B, S, d = xt.shape
    tiles = tuning.moe_dispatch_tiles(d, xt.dtype)
    bm, bk = tiles["block"]
    M = E * C

    if isinstance(flat_slot, jax.core.Tracer):
        # static shapes under jit/scan: the stream is the full grid, block
        # values come from the (traced) dense dispatch matrix.  The index
        # stream stays host-side numpy: it is routing-independent here and
        # the engine inspects it with numpy before the call.
        tiles4, Mp, Sp = _dispatch_matrix_tiles(flat_slot, S, E, C, bm, bk,
                                                xt.dtype)
        gm, gn = Mp // bm, Sp // bk
        brows, bcols = np.nonzero(np.ones((gm, gn), bool))
        indptr = np.zeros(gm + 1, np.int32)
        np.cumsum(np.bincount(brows, minlength=gm), out=indptr[1:])
        ab = BatchedBCSR(indptr=indptr,
                         block_rows=brows.astype(np.int32),
                         block_cols=bcols.astype(np.int32),
                         blocks=tiles4[:, brows, bcols],
                         shape=(B, Mp, Sp), block=(bm, bk))
    else:
        ab, _, _ = _build_routed_stream(flat_slot, S, E, C, bm, bk,
                                        xt.dtype)
        Sp = ab.shape[2]
    xt_p = jnp.pad(xt, ((0, 0), (0, Sp - S), (0, 0)))
    out = engine.shard_spmm_batched(ab, xt_p, bn=tiles["bn"],
                                    nt=tiles["nt"],
                                    out_dtype=xt.dtype)      # (B, Mp, d)
    return out[:, :M].reshape(B, E, C, d).transpose(1, 0, 2, 3)


def _combine_gather(yt: jax.Array, flat_slot: jax.Array, gate: jax.Array,
                    keep: jax.Array, E: int, C: int):
    """Gather each token's expert output back by its own index; dropped
    tokens contribute zero.  yt: (B, E*C, d) -> (B, S, d)."""
    B = yt.shape[0]
    d = yt.shape[-1]
    yt_pad = jnp.concatenate([yt, jnp.zeros((B, 1, d), yt.dtype)], axis=1)
    back = jnp.take_along_axis(
        yt_pad, jnp.minimum(flat_slot, E * C)[..., None], axis=1)
    return back * (gate * keep).astype(back.dtype)[..., None]


# --------------------------------------------------------------- the layer --

def apply_moe(p, x, cfg: ArchConfig, *, counts: Optional[jax.Array] = None,
              pos=None, groups: Optional[int] = None,
              dispatch: Optional[str] = None):
    """x: (B, S, d) -> ((B, S, d), new_counts (B, E) int32).

    ``counts``/``pos`` thread the routing state for stepwise decode: pass the
    previous call's ``new_counts`` and the absolute position of x[:, 0] and a
    one-token step reproduces the prefill slot and drop decision bit-for-bit.
    Training/prefill callers pass neither (fresh sequence at position 0) and
    may discard the returned counts.

    ``dispatch`` selects the backend ("gather" | "bcsr"); default is
    ``context.MOE_DISPATCH`` then ``cfg.moe_dispatch``.

    Routing is per batch row, so under dp sharding of B the cumsum stays
    shard-local and the only cross-shard movement is the (E, B, C, d)
    dispatch -- the EP all-to-all.  ``groups`` (or ``context.MOE_GROUPS``)
    declares how many row groups the data axes expect; when it does not
    divide B the dispatch buffer cannot align with the data shards and the
    layer warns (raises under ``cfg.moe_strict_dispatch``) instead of
    silently falling back to an unaligned layout.
    """
    from repro.parallel import context as pctx
    from repro.parallel.sharding import constrain

    if cfg.moe_dropless:
        return apply_moe_dropless(p, x, cfg)
    B, S, d = x.shape
    E = cfg.n_experts

    if pctx.MOE_IMPL == "shard_map" and pctx.MESH is not None:
        # train-only path: each (row, sequence-shard) chunk routes locally,
        # occupancy is NOT threaded across calls, and dispatch is always the
        # gather formulation.  A caller carrying routing state (decode) or
        # requesting the bcsr backend would silently lose prefix stability,
        # so that is an error in spirit -- surface it.
        backend = dispatch or pctx.MOE_DISPATCH or cfg.moe_dispatch
        if counts is not None or pos is not None or backend != "gather":
            msg = ("apply_moe: the shard_map impl is train-only -- it does "
                   "not thread routing occupancy (counts/pos) and only "
                   "supports moe_dispatch='gather'; decode and bcsr callers "
                   "must use the pjit impl.")
            if cfg.moe_strict_dispatch:
                raise ValueError(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        from repro.models.moe_shard_map import apply_moe_shard_map
        from repro.parallel.sharding import FSDP
        dp_axes = tuple(a for a in FSDP if a in pctx.MESH.axis_names)
        dp_axes = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        out = apply_moe_shard_map(p, x, cfg, pctx.MESH, dp_axes=dp_axes,
                                  tp_axis="model")
        new_counts = counts if counts is not None else jnp.zeros((B, E), jnp.int32)
        return out, new_counts

    _check_groups(B, cfg, groups or pctx.MOE_GROUPS, "apply_moe")

    pos0 = 0 if pos is None else pos
    r = route_tokens(p["router"], x, cfg, counts=counts, pos0=pos0)
    C = dispatch_capacity(S, cfg, pos0=pos0)

    # --- SU dispatch: index stream (expert*C + within) per row -------------
    flat_slot = jnp.where(r.keep, r.expert_id * C + r.within, E * C)
    backend = dispatch or pctx.MOE_DISPATCH or cfg.moe_dispatch
    if backend == "bcsr":
        xe = _dispatch_bcsr(x, flat_slot, E, C)
    elif backend == "gather":
        xe = _dispatch_gather(x, flat_slot, E, C)
    else:
        raise ValueError(f"unknown moe_dispatch backend {backend!r}")
    out = _moe_tail(p, x, xe, r.gate, r.keep, flat_slot, cfg, E, C)
    return out, r.new_counts


def _check_groups(B: int, cfg: ArchConfig, G: Optional[int], who: str):
    if G and B % G != 0:
        msg = (f"{who}: {G} dispatch group(s) requested but the batch "
               f"dim B={B} is not divisible; the (E, B, C, d) dispatch "
               "buffer cannot align with the data shards and falls back to "
               "an ungrouped layout (extra resharding under pjit).")
        if cfg.moe_strict_dispatch:
            raise ValueError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _moe_tail(p, x, xe, gate, keep, flat_slot, cfg: ArchConfig, E: int,
              C: int):
    """Expert FFN + combine (+ shared expert): everything downstream of the
    dispatch buffer."""
    from repro.parallel import context as pctx
    from repro.parallel.sharding import constrain

    B, S, d = x.shape
    if pctx.MOE_SPEC is not None:
        xe = constrain(xe, pctx.MOE_SPEC)                 # EP all-to-all

    ye = _expert_ffn(p["experts"], xe.reshape(E, B * C, d),
                     cfg.mlp_type).reshape(E, B, C, d)

    # --- SU combine: inverse all-to-all + gather back by the same stream ---
    # Constrain BACK to the dispatch (row-sharded) layout before the gather:
    # each token's result lives on exactly one expert shard, so the reshard is
    # an all-to-all; gathering straight from the EP layout instead makes GSPMD
    # emit a full-activation all-reduce per layer (measured: 5.4 GB -> 34 MB
    # per layer on llama4-scout train_4k).
    yt = ye.transpose(1, 0, 2, 3).reshape(B, E * C, d)
    if pctx.MOE_COMBINE_SPEC is not None:
        yt = constrain(yt, pctx.MOE_COMBINE_SPEC)
    out = _combine_gather(yt, flat_slot, gate, keep, E, C)

    if cfg.moe_shared_expert:
        out = out + apply_mlp(p["shared"], x.reshape(B * S, d),
                              cfg).reshape(B, S, d)
    return out


def load_balance_loss(logits: jax.Array, expert_id: jax.Array, E: int):
    """Switch-style auxiliary loss (fraction-routed x mean-prob)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    frac = jnp.mean(jax.nn.one_hot(expert_id, E, dtype=jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac * mean_p)
