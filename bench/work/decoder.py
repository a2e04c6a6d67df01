"""Operations a dense decoder's forward pass needs, from its shapes alone.

Two operations (a multiply and an add) per weight per token for every matrix
multiplication, and 2 per element for the two attention products (scores and
the weighted sum of values) over the positions a token attends to.  Norms,
RoPE, softmax and the activation are left out, as the usual model-FLOPs
count does.  What the program computes beyond this (padded batch rows,
attention over the whole cache length) is not counted: it is not useful work.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that one token multiplies, unembedding included."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ff = cfg["intermediate_size"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = 3 * d * ff
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def token_flops(cfg: dict, position: int) -> int:
    """FLOPs of one token at 0-based ``position`` (it attends to
    ``position + 1`` keys, itself included)."""
    attn = 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * (position + 1)
    return 2 * matmul_params(cfg) + attn


def span_flops(cfg: dict, start: int, stop: int) -> int:
    """FLOPs of the tokens at positions ``start .. stop - 1``: a prefill of
    a prompt is ``span_flops(cfg, 0, len)``, one decode step of a request
    at position p is ``span_flops(cfg, p, p + 1)``."""
    n = stop - start
    if n <= 0:
        return 0
    keys = (start + 1 + stop) * n // 2          # sum of (p + 1) over the span
    return 2 * matmul_params(cfg) * n + 4 * cfg["num_hidden_layers"] \
        * cfg["num_attention_heads"] * cfg["head_dim"] * keys
