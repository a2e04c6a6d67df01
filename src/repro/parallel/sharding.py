"""Sharding rules: FSDP x TP x EP x SP over the ("pod","data","model") mesh.

Discipline (the chiplet/D2D analogue from DESIGN.md S5):
* FSDP: every large parameter is sharded over the combined ("pod","data")
  axes *and* over "model" (2-D sharded matrices) -- ZeRO-3: optimizer states
  mirror the param specs.
* TP ("model"): head/ff/vocab/expert dims.
* EP: expert dim of MoE weights over "model"; token dispatch becomes an
  all-to-all under pjit.
* SP: when the batch is too small to fill the data axes (long-context
  decode), the sequence dim of activations/caches shards over "data".

Specs are derived from the *param tree paths*, so any pytree that mirrors the
params (grads, AdamW m/v) reuses the same rules verbatim.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

FSDP: Tuple[str, ...] = ("pod", "data")   # present axes are filtered per mesh
TP = "model"


def _filter(spec: P, mesh) -> P:
    """Drop mesh axes that don't exist (single-pod mesh has no 'pod')."""
    names = set(mesh.axis_names)

    def f(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in names else None
        sub = tuple(a for a in entry if a in names)
        return sub if len(sub) > 1 else (sub[0] if sub else None)

    return P(*(f(e) for e in spec))


def _rule_for(path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
    """Map a param path (dict keys along the pytree) + shape to a spec.

    Scanned block params carry a leading repeat dim -> prepend None.
    """
    name = path[-1]
    parent = path[-2] if len(path) >= 2 else ""

    base = {
        # embeddings
        "embed": P(TP, FSDP),
        "unembed": P(FSDP, TP),
        # attention
        "wq": P(FSDP, TP), "wk": P(FSDP, TP), "wv": P(FSDP, TP),
        "wo": P(TP, FSDP),
        "bq": P(TP), "bk": P(TP), "bv": P(TP),
        # dense mlp
        "w_gate": P(FSDP, TP), "w_up": P(FSDP, TP), "w_down": P(TP, FSDP),
        # router
        "router": P(FSDP, None),
        # mamba
        "w_in": P(FSDP, TP), "w_out": P(TP, FSDP),
        "conv_w": P(None, TP), "conv_b": P(TP),
        "a_log": P(None), "d_skip": P(None), "dt_bias": P(None),
        # rwkv
        "w_r": P(FSDP, TP), "w_k": P(FSDP, TP), "w_v": P(FSDP, TP),
        "w_g": P(FSDP, TP), "w_o": P(TP, FSDP),
        "w_ck": P(FSDP, TP), "w_cv": P(TP, FSDP), "w_cr": P(FSDP, TP),
        "decay_lora_a": P(FSDP, None), "decay_lora_b": P(None, FSDP),
        "mu": P(None, FSDP), "mu_c": P(None, FSDP),
        "decay_base": P(FSDP), "bonus_u": P(None, None),
        # norms / scalars
        "scale": P(None),
    }
    spec = base.get(name)
    if spec is None:
        spec = P(*([None] * len(shape)))
    if parent == "experts":
        # MoE expert weights (E, d, ff): EP over model, FSDP over d/ff
        if name in ("w_gate", "w_up"):
            spec = P(TP, FSDP, None)
        elif name == "w_down":
            spec = P(TP, None, FSDP)
    # leading stacked-repeat dim?
    ndim_spec = len(spec)
    if len(shape) == ndim_spec + 1:
        spec = P(None, *spec)
    elif len(shape) != ndim_spec:
        spec = P(*([None] * len(shape)))
    return spec


def param_specs(params, mesh) -> Any:
    """PartitionSpec tree mirroring ``params``."""

    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        if isinstance(node, (tuple, list)):
            out = [walk(path + (str(i),), v) for i, v in enumerate(node)]
            return type(node)(out)
        return _filter(_rule_for(path, node.shape), mesh)

    return walk((), params)


def shardings(spec_tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_specs(param_spec_tree, mesh):
    """AdamWState(m, v, count) mirrors the params; count replicated."""
    from repro.optim.adamw import AdamWState
    return AdamWState(m=param_spec_tree, v=param_spec_tree, count=P())


def batch_spec(batch: int, mesh, *, seq_shard: bool = False) -> P:
    """Tokens (B, S): batch over ("pod","data") when it divides; otherwise
    shard the sequence (SP) instead."""
    dp = _filter(P(FSDP), mesh)[0]
    if seq_shard:
        return P(None, "data" if "data" in mesh.axis_names else None)
    return P(dp, None)


def data_axis_size(mesh) -> int:
    n = 1
    for a in FSDP:
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def cache_specs(cache, cfg, mesh, *, batch: int) -> Any:
    """Decode-cache specs. Batch dim shards over ("pod","data") when
    possible; otherwise (long-context, B=1) the sequence/state dims shard:
    attention K/V over "data" (SP decode -- distributed online-softmax
    merge is inserted by SPMD), ssm/wkv head dims over "model"."""
    dp_size = data_axis_size(mesh)
    batch_ok = batch % dp_size == 0 and batch >= dp_size
    dp = _filter(P(FSDP), mesh)[0] if batch_ok else None

    def leaf_spec(path, x):
        name = path[-1]
        if name in ("k", "v"):
            # (L, B, Hkv, S, hd): batch over dp, sequence over "model" (the
            # online-softmax merge over seq shards is inserted by SPMD);
            # long-context (batch too small) shards seq over "data" instead.
            if batch_ok:
                return P(None, dp, None, TP, None)
            return P(None, None, None, "data", None)
        if name == "ssm":      # (L, B, nh, hd, ns)
            return P(None, dp, TP, None, None)
        if name == "wkv":      # (L, B, nh, hd, hd)
            return P(None, dp, TP, None, None)
        if name == "conv":     # (L, B, K-1, C)
            return P(None, dp, None, TP)
        if name in ("shift_t", "shift_c"):  # (L, B, 1, d)
            return P(None, dp, None, None)
        return P(*([None] * x.ndim))

    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        if isinstance(node, (tuple, list)):
            return type(node)(walk(path + (str(i),), v) for i, v in enumerate(node))
        return _filter(leaf_spec(path, node), mesh)

    return walk((), cache)


def constrain(x, spec: P):
    """``with_sharding_constraint`` against the mesh set by ``jax.set_mesh``;
    a no-op when no mesh is set.  Any other failure raises."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
