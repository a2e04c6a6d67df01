"""Qwen3-Next-80B-A3B: hybrid linear-attention MoE.
[hf:Qwen/Qwen3-Next-80B-A3B-Instruct config.json, model_type qwen3_next]
48L d_model=2048; per period of 4 layers, 3 Gated DeltaNet (16 key / 32
value heads of 128, conv 4) and 1 gated full attention (16 q / 2 kv heads of
256, sigmoid output gate, qk-norm, RoPE on 25% of each head, theta 1e7).
Every layer is MoE: 512 experts of width 512, top-10 renormalised, plus one
SwiGLU shared expert of width 512 gated by sigmoid(x w).  vocab=151936,
untied.

CONFIG is the published model as one chip of a 32-way expert-parallel
deployment holds it: the router scores all 512 experts and the layer computes
the 16 held here (experts 0..15).  The multi-token-prediction module is not in
config.json and is left out."""
from repro.models.config import ArchConfig

_UNIT = ("gdn+moe", "gdn+moe", "gdn+moe", "attn+moe")

CONFIG = ArchConfig(
    name="qwen3-next-80b-a3b", family="hybrid",
    d_model=2048, n_heads=16, n_kv_heads=2, d_ff=5120, vocab_size=151936,
    block_unit=_UNIT, n_repeats=12, head_dim=256,
    qk_norm=True, rope_theta=1e7, rope_fraction=0.25, attn_output_gate=True,
    n_experts=512, top_k=10, experts_held=16,
    d_expert=512, d_shared_expert=512, moe_shared_expert=True,
    moe_shared_gate=True, mlp_type="swiglu",
    gdn_k_heads=16, gdn_v_heads=32, gdn_k_head_dim=128, gdn_v_head_dim=128,
    gdn_conv=4,
)

SMOKE = ArchConfig(
    name="qwen3-next-smoke", family="hybrid",
    d_model=64, n_heads=4, n_kv_heads=2, d_ff=96, vocab_size=256,
    block_unit=_UNIT, n_repeats=1, head_dim=16,
    qk_norm=True, rope_theta=1e7, rope_fraction=0.25, attn_output_gate=True,
    n_experts=16, top_k=4, experts_held=8,
    d_expert=32, d_shared_expert=32, moe_shared_expert=True,
    moe_shared_gate=True, mlp_type="swiglu",
    gdn_k_heads=2, gdn_v_heads=4, gdn_k_head_dim=16, gdn_v_head_dim=16,
    gdn_conv=4,
)
