"""shard_map MoE: explicit EP all-to-all dispatch (the optimized path).

Hypothesis (EXPERIMENTS.md SPerf-C): under pjit, the combine gather's
*backward* is a scatter-add of model-sharded cotangents into a data-sharded
buffer, which GSPMD lowers to a full-activation f32 all-reduce per MoE layer
(~193 GB/step on llama4-scout train_4k). Writing the dispatch as an explicit
``jax.lax.all_to_all`` inside ``shard_map`` bounds the traffic to the
capacity buffers by construction -- and ``all_to_all``'s transpose is
``all_to_all``, so the backward moves the same bounded bytes.

Routing reuses the prefix-stable stage from ``repro.models.moe``
(:func:`~repro.models.moe.route_tokens`) on the *local* (B_loc, S_loc)
block, so the slot/drop law is the same per-(row, expert) prefix-cumsum law
as the pjit path.  This impl is train-only: sequence shards route their
local chunk from local position 0 and routing state is not threaded across
calls (decode uses the pjit path, which carries occupancy counts).

Layout inside shard_map (mesh axes dp = ("pod","data") merged, tp = "model"):
  x block: (B_loc, S_loc, d)  [B over dp, S over tp (SP)]
  experts: E split over tp; d split over dp (FSDP -> all_gather on entry,
           psum_scatter on the gradient by AD of all_gather).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models.moe import (_combine_gather, _dispatch_gather,
                              dispatch_capacity, route_tokens)


def apply_moe_shard_map(p, x, cfg: ArchConfig, mesh, *, dp_axes, tp_axis):
    """x: (B, S, d) -> (B, S, d) with explicit EP all-to-all."""
    E = cfg.n_experts
    tp = mesh.shape[tp_axis]
    assert E % tp == 0, (E, tp)

    def body(x_blk, router, experts, shared):
        # x_blk: (B_loc, S_loc, d) -- local tokens, routed per local row
        Bl, Sl, d = x_blk.shape
        r = route_tokens(router, x_blk, cfg)
        cap = dispatch_capacity(Sl, cfg)
        flat = jnp.where(r.keep, r.expert_id * cap + r.within, E * cap)
        xe = _dispatch_gather(x_blk, flat, E, cap)       # (E, Bl, cap, d)
        xe = xe.reshape(E, Bl * cap, d)

        # EP all-to-all: split the expert dim over tp peers, concat capacity.
        # (E, Bl*cap, d) -> (E/tp, tp*Bl*cap, d): this shard now holds *its*
        # experts' tokens from every sequence-peer. all_to_all's transpose is
        # all_to_all -> bounded backward traffic by construction.
        xe = jax.lax.all_to_all(xe, tp_axis, 0, 1, tiled=True)

        # FSDP gather of this shard's expert weights over dp (bf16 operands;
        # AD turns this into psum_scatter on the weight gradient = ZeRO-3)
        cd = x_blk.dtype
        gather_axis = {"w_gate": 1, "w_up": 1, "w_down": 2}
        w = {k: jax.lax.all_gather(v.astype(cd), dp_axes,
                                   axis=gather_axis[k], tiled=True)
             for k, v in experts.items()}
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w["w_gate"])) \
            * jnp.einsum("ecd,edf->ecf", xe, w["w_up"]) \
            if cfg.mlp_type == "swiglu" else \
            jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", xe, w["w_up"])))
        ye = jnp.einsum("ecf,efd->ecd", h, w["w_down"])

        # inverse all-to-all back to the dispatch layout
        ye = jax.lax.all_to_all(ye, tp_axis, 1, 0, tiled=True)
        yt = ye.reshape(E, Bl, cap, d).transpose(1, 0, 2, 3).reshape(
            Bl, E * cap, d)
        out = _combine_gather(yt, flat, r.gate, r.keep, E, cap)
        if cfg.moe_shared_expert:
            sh = {k: jax.lax.all_gather(v.astype(cd), dp_axes, axis=0,
                                        tiled=True)
                  for k, v in shared.items()}
            # shared expert weights are (d, ff)/(ff, d) FSDP-sharded on dim 0
            xt = x_blk.reshape(Bl * Sl, d)
            hh = jax.nn.silu(xt @ sh["w_gate"]) * (xt @ sh["w_up"]) \
                if cfg.mlp_type == "swiglu" else \
                jnp.square(jax.nn.relu(xt @ sh["w_up"]))
            out = out + (hh @ sh["w_down"]).reshape(Bl, Sl, d)
        return out

    dp = dp_axes
    shared = p.get("shared", {k: jnp.zeros((), x.dtype) for k in ()}) or {}
    in_specs = (
        P(dp, tp_axis, None),                        # x: B over dp, S over tp
        P(None, None),                               # router replicated
        {k: P(tp_axis, dp, None) if k in ("w_gate", "w_up")
         else P(tp_axis, None, dp) for k in p["experts"]},
        {k: P(dp, None) if k in ("w_gate", "w_up") else P(dp, None)
         for k in shared},
    )
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=P(dp, tp_axis, None))
    return fn(x, p["router"], p["experts"], shared)
