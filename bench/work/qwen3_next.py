"""Operations and bytes of Qwen3-Next's forward pass, from its shapes alone.

Two operations (a multiply and an add) per weight per token for every
matrix multiplication that every token makes: the Gated DeltaNet and
attention projections, the router over all its experts, the gated shared
expert and the unembedding.  Routed experts are counted by the (token, held
expert) pairs the program reports computing (``moe_held_pairs``), two
operations per weight of one expert a pair, since this chip computes only
its share.  Mixing: 2 per element for attention's two products over the
positions a token attends to; for the recurrence, per value head and token,
the three contractions with the (Dk, Dv) state (S^T k, the rank-one update,
S^T q), 6 Dk Dv.  Norms, the conv, gates and activations are left out, as
the usual model-FLOPs count does.  Padded rows and attention over the
cache's empty tail are not useful work and are not counted.

The decode kernel ``gdn_decode`` moves, per (row, value head) and call, its
float32 state in and out and its query, key, value and output vectors and
two scalars: the least bytes a call needs.
"""
from __future__ import annotations


def _layers(cfg: dict):
    """(Gated DeltaNet layers, attention layers)."""
    n = cfg["num_hidden_layers"]
    attn = n // cfg["full_attention_interval"]
    return n - attn, attn


def matmul_params(cfg: dict) -> int:
    """Weights that every token multiplies, unembedding included."""
    d = cfg["hidden_size"]
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    gdn = d * (2 * kd + 2 * vd) + d * 2 * cfg["linear_num_value_heads"] \
        + vd * d
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    attn = d * 2 * H * hd + 2 * d * K * hd + H * hd * d
    moe = d * cfg["deployment"]["router_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] + d
    n_gdn, n_attn = _layers(cfg)
    return n_gdn * gdn + n_attn * attn \
        + cfg["num_hidden_layers"] * moe + d * cfg["vocab_size"]


def recurrence_flops(cfg: dict) -> int:
    """Per token, over the Gated DeltaNet layers."""
    n_gdn, _ = _layers(cfg)
    return n_gdn * 6 * cfg["linear_num_value_heads"] \
        * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def span_flops(cfg: dict, start: int, stop: int) -> int:
    """FLOPs of the tokens at positions ``start .. stop - 1`` without the
    routed experts."""
    n = stop - start
    if n <= 0:
        return 0
    keys = (start + 1 + stop) * n // 2          # sum of (p + 1) over the span
    _, n_attn = _layers(cfg)
    return (2 * matmul_params(cfg) + recurrence_flops(cfg)) * n \
        + 4 * n_attn * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def pair_flops(cfg: dict, pairs: int) -> int:
    """FLOPs of ``pairs`` (token, held expert) pairs."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * pairs


def gdn_decode_bytes(cfg: dict, rows: int) -> int:
    """HBM bytes of one ``gdn_decode`` call over ``rows`` batch rows."""
    K, V = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per_head = 4 * (2 * K * V + 2 * K + 2 * V + 2)
    return rows * cfg["linear_num_value_heads"] * per_head
