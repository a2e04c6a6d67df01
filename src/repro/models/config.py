"""Architecture configuration: one frozen dataclass drives the whole stack.

A model is a scanned stack of *superblocks* (the repeating unit). Each
superblock is a tuple of sub-layer kinds, so heterogeneous-but-periodic
stacks (Gemma-3's 5 local : 1 global, Llama-4's dense/MoE alternation,
Zamba-2's shared-attention insertions, Qwen3-Next's 3 Gated DeltaNet : 1
gated attention) scan homogeneously: params are stacked along the repeat
axis and `lax.scan` keeps the HLO one-superblock small.

Layer kinds: ``attn`` (GQA attention + MLP), ``attn_local`` (sliding
window), ``attn_global``, ``attn+moe`` (attention + routed experts),
``gdn+moe`` (Gated DeltaNet linear attention + routed experts, see
``models/gdn.py``), ``mamba`` (Mamba2 mixer), ``rwkv`` (RWKV-6 time and
channel mix), and ``shared_attn`` (Zamba's one shared block).  Attention
options (``attn_output_gate``, ``rope_fraction``, ``qk_norm``) and MoE
options (``top_k``, ``experts_held``, ``d_expert``,
``moe_shared_gate``) apply to every layer of the kinds that have them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

LayerKind = str  # attn | attn_local | attn_global | mamba | rwkv | attn+moe | gdn+moe


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | hybrid | ssm | audio | vlm
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    # stack structure
    block_unit: Tuple[LayerKind, ...]  # the repeating superblock
    n_repeats: int                     # stack = block_unit * n_repeats
    head_dim: Optional[int] = None     # default d_model // n_heads
    # attention options
    qk_norm: bool = False
    qkv_bias: bool = False
    local_window: Optional[int] = None   # for attn_local layers
    rope_theta: float = 1e6
    # RoPE on the first rope_fraction of each head's dims (rotate-half
    # within them), the rest passed through (Qwen3-Next: 0.25)
    rope_fraction: float = 1.0
    # sigmoid output gate per head: q projection emits [query | gate] per
    # head and the attention output is multiplied by sigmoid(gate) before
    # the output projection (Qwen3-Next's full-attention layers)
    attn_output_gate: bool = False
    # mlp
    mlp_type: str = "swiglu"             # swiglu | squared_relu
    # moe
    n_experts: int = 0
    top_k: int = 1
    capacity_factor: float = 1.25
    moe_shared_expert: bool = False      # Llama-4 style always-on shared expert
    # routed expert and shared expert widths (None = d_ff)
    d_expert: Optional[int] = None
    d_shared_expert: Optional[int] = None
    # shared expert output scaled by sigmoid(x @ w_gate), w_gate (d, 1)
    moe_shared_gate: bool = False
    # the expert share this chip holds: experts [expert_offset,
    # expert_offset + experts_held) of the n_experts the router scores
    # (None = all).  Expert parallelism's one-chip face: the router keeps
    # its full width and top_k, only the held experts are computed.
    experts_held: Optional[int] = None
    expert_offset: int = 0
    # dispatch backend: "gather" (SU index-stream gather) or "bcsr" (dispatch
    # matrix as BatchedBCSR through the sharded SpMM Pallas kernel); may be
    # overridden per-trace via repro.parallel.context.MOE_DISPATCH
    moe_dispatch: str = "gather"
    # raise (instead of warn) when the requested dispatch grouping cannot
    # align with the batch dim -- see models.moe.apply_moe
    moe_strict_dispatch: bool = False
    # ssm (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    # gated deltanet (gdn+moe layers): key/value heads, head dims, conv width
    gdn_k_heads: int = 0
    gdn_v_heads: int = 0
    gdn_k_head_dim: int = 128
    gdn_v_head_dim: int = 128
    gdn_conv: int = 4
    # zamba-style shared block: apply a single shared attention block after
    # every `shared_attn_every` scanned steps (0 = never)
    shared_attn_every: int = 0
    # extra leading layers of kind block_unit[0] outside the main scan (used
    # to hit exact layer counts, e.g. zamba2's 38 = 2 + 6*6)
    n_prologue: int = 0
    # frontend stubs: 'none' | 'vision' | 'audio' -- input_specs() then expects
    # precomputed patch/frame embeddings alongside (or instead of) tokens
    frontend: str = "none"
    frontend_tokens: int = 0             # prepended embedding positions
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # dtype policy name from repro.core.precision
    policy: str = "bf16"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows padded to a TP/FSDP-shardable multiple (256
        divides every production mesh axis product used here). Logits over
        padded ids are masked in the loss and sliced off in serving."""
        return -(-self.vocab_size // 256) * 256

    @property
    def n_layers(self) -> int:
        return len(self.block_unit) * self.n_repeats + self.n_prologue

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def moe_dropless(self) -> bool:
        """The routing rule follows ``top_k``: top-1 routes with the prefix
        capacity (Llama-4 style, see models.moe); top-k routes dropless --
        softmax over all n_experts in f32, top_k, the k weights
        renormalised to sum 1, no capacity."""
        return self.top_k > 1

    @property
    def ff_expert(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def ff_shared(self) -> int:
        return self.d_shared_expert or self.d_ff

    @property
    def n_held(self) -> int:
        """Routed experts whose weights live here."""
        return self.n_experts if self.experts_held is None \
            else self.experts_held

    def _ffn_params(self, width: int) -> int:
        return (3 if self.mlp_type == "swiglu" else 2) * self.d_model * width

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + stacked blocks) of the
        weights held here: routed experts count ``n_held`` times."""
        d, ff, V = self.d_model, self.d_ff, self.vocab_size
        hd, Hq, Hkv = self.hd, self.n_heads, self.n_kv_heads
        n = V * d                      # embedding
        if not self.tie_embeddings:
            n += V * d                 # unembedding
        per_kind = {}
        q_out = Hq * hd * (2 if self.attn_output_gate else 1)
        attn = d * q_out + 2 * d * (Hkv * hd) + (Hq * hd) * d
        if self.qkv_bias:
            attn += (Hq + 2 * Hkv) * hd
        if self.qk_norm:
            attn += 2 * hd
        mlp = self._ffn_params(ff)
        per_kind["attn"] = attn + mlp + 2 * d
        per_kind["attn_local"] = per_kind["attn_global"] = per_kind["attn"]
        moe_ffn = self.n_held * self._ffn_params(self.ff_expert) \
            + d * self.n_experts
        if self.moe_shared_expert:
            moe_ffn += self._ffn_params(self.ff_shared)
            if self.moe_shared_gate:
                moe_ffn += d
        per_kind["attn+moe"] = attn + moe_ffn + 2 * d
        kd = self.gdn_k_heads * self.gdn_k_head_dim
        vd = self.gdn_v_heads * self.gdn_v_head_dim
        gdn = d * (2 * kd + 2 * vd) + d * 2 * self.gdn_v_heads \
            + self.gdn_conv * (2 * kd + vd) + 2 * self.gdn_v_heads \
            + self.gdn_v_head_dim + vd * d
        per_kind["gdn+moe"] = gdn + moe_ffn + 2 * d
        d_in = self.ssm_expand * d
        nh = d_in // self.ssm_head_dim
        mamba = d * (2 * d_in + 2 * self.ssm_state + nh) \
            + self.ssm_conv * (d_in + 2 * self.ssm_state) \
            + d_in * d + 2 * nh + d_in
        per_kind["mamba"] = mamba + d
        per_kind["rwkv"] = int(d * ff * 2 + d * d * 5 + 2 * d)  # see rwkv6.py
        for kind in self.block_unit:
            n += per_kind[kind] * self.n_repeats
        if self.n_prologue:
            n += per_kind[self.block_unit[0]] * self.n_prologue
        if self.shared_attn_every:
            n += per_kind["attn"]      # one shared block, reused
        return int(n)

    def active_param_count(self) -> int:
        """Active params per token (= total for dense; routed subset for
        MoE): of the held experts, ``top_k * n_held / n_experts`` on average
        (all ``top_k`` when every expert is held)."""
        if self.n_experts == 0:
            return self.param_count()
        w = self._ffn_params(self.ff_expert)
        active = self.top_k * self.n_held / self.n_experts
        inactive = (self.n_held - active) * w
        n_moe_layers = sum(k.endswith("+moe")
                           for k in self.block_unit) * self.n_repeats
        return int(self.param_count() - inactive * n_moe_layers)
