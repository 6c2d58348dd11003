"""Unified model configuration covering all assigned architecture families
(dense / MoE / SSM / hybrid / enc-dec / VLM / Gated DeltaNet hybrid)."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm | gdn
    n_layers: int
    d_model: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 32000

    # attention variants
    qk_norm: bool = False
    rope_theta: float = 1e4
    window: int = 0              # sliding-window size for local layers (0 = full)
    local_global_ratio: int = 0  # gemma3: N local layers per global layer
    attn_logit_softcap: float = 0.0
    tie_embeddings: bool = False
    attn_output_gate: bool = False      # qwen3-next: out = (o * sigmoid(g)) W_o
    partial_rotary_factor: float = 1.0  # RoPE on the first share of each head
    norm_zero_centred: bool = False     # RMSNorm scales by (1 + w)

    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    shared_expert: bool = False
    capacity_factor: float = 1.25
    moe_every: int = 1        # llama4: 2 => alternate dense/MoE layers
    shared_expert_gate: bool = False    # shared expert behind sigmoid(x w)
    # experts held here, [expert_offset, +n_local_experts) of the n_experts
    # routed over (0 = all); the gdn family's expert layer is dropless
    expert_offset: int = 0
    n_local_experts: int = 0

    # SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    ssm_chunk: int = 256
    attn_every: int = 0          # hybrid: shared attention block period

    # Gated DeltaNet hybrid (gdn): full attention where (i+1) % every == 0
    full_attn_every: int = 0
    lin_k_heads: int = 0
    lin_v_heads: int = 0
    lin_head_dim: int = 128      # key and value head size

    # enc-dec
    enc_layers: int = 0
    # vlm
    cross_attn_every: int = 0
    n_patches: int = 0

    dtype: str = "bfloat16"
    norm_eps: float = 1e-6

    # ----------------------------------------------------------------- #
    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def ssm_heads(self) -> int:
        return (self.ssm_expand * self.d_model) // self.ssm_head_dim if self.ssm_state else 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model if self.ssm_state else 0

    @property
    def n_held_experts(self) -> int:
        """Experts this chip holds of each expert layer."""
        return self.n_local_experts or self.n_experts

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def n_full_layers(self) -> int:
        return self.n_layers // self.full_attn_every if self.family == "gdn" else 0

    @property
    def lin_conv_dim(self) -> int:
        """Channels of the Gated DeltaNet convolution: q, k and v."""
        return (2 * self.lin_k_heads + self.lin_v_heads) * self.lin_head_dim

    def validate(self) -> "ModelConfig":
        assert self.family in ("dense", "moe", "ssm", "hybrid", "encdec", "vlm",
                               "gdn"), self.family
        if self.family in ("dense", "moe", "encdec", "vlm", "gdn"):
            assert self.n_heads > 0 and self.head_dim > 0
            assert self.n_heads % max(1, self.n_kv_heads) == 0
        if self.family == "moe":
            assert self.n_experts > 0 and self.top_k > 0 and self.d_expert > 0
        if self.family in ("ssm", "hybrid"):
            assert self.ssm_state > 0
            assert self.d_inner % self.ssm_head_dim == 0
        if self.family == "gdn":
            assert self.n_experts > 0 and self.top_k > 0
            assert self.full_attn_every > 1 and self.n_layers % self.full_attn_every == 0
            assert self.lin_k_heads > 0 and self.lin_v_heads % self.lin_k_heads == 0
        assert 0 <= self.expert_offset
        assert self.expert_offset + self.n_held_experts <= self.n_experts
        return self

    def reduced(self, **overrides) -> "ModelConfig":
        """A small same-family config for CPU smoke tests."""
        base = dict(
            n_layers=min(self.n_layers, 4),
            d_model=128,
            n_heads=4 if self.n_heads else 0,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads else 0,
            head_dim=32 if self.n_heads else 0,
            d_ff=256 if self.d_ff else 0,
            vocab_size=512,
            n_experts=min(self.n_experts, 8) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            d_expert=64 if self.d_expert else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=32 if self.ssm_state else 64,
            ssm_chunk=32,
            enc_layers=min(self.enc_layers, 2) if self.enc_layers else 0,
            n_patches=16 if self.n_patches else 0,
            window=min(self.window, 64) if self.window else 0,
            attn_every=2 if self.attn_every else 0,
            lin_k_heads=min(self.lin_k_heads, 2),
            lin_v_heads=min(self.lin_v_heads, 4),
            lin_head_dim=32 if self.lin_k_heads else 128,
            n_local_experts=0,
            expert_offset=0,
            cross_attn_every=2 if self.cross_attn_every else 0,
            dtype="float32",
            name=self.name + "-smoke",
        )
        # keep MHA for models whose kv == heads
        if self.n_kv_heads and self.n_kv_heads == self.n_heads:
            base["n_kv_heads"] = base["n_heads"]
        base.update(overrides)
        return dataclasses.replace(self, **base).validate()

    # -- parameter counting (for roofline MODEL_FLOPS = 6*N*D) ----------- #
    def param_count(self, active_only: bool = False) -> int:
        """Approximate parameter count; ``active_only`` counts MoE experts
        at top_k/n_experts weight (for 6*N_active*D)."""
        d, v = self.d_model, self.vocab_size
        emb = v * d * (1 if self.tie_embeddings else 2)
        att = 0
        if self.n_heads:
            q = d * self.n_heads * self.head_dim
            kv = 2 * d * self.n_kv_heads * self.head_dim
            o = self.n_heads * self.head_dim * d
            att = q + kv + o
        ffn = 3 * d * self.d_ff if self.d_ff else 0
        moe = 0
        if self.n_experts:
            per_expert = 3 * d * self.d_expert
            n_eff = self.top_k if active_only else self.n_experts
            moe = per_expert * n_eff + d * self.n_experts  # + router
            if self.shared_expert:
                moe += 3 * d * self.d_ff if self.d_ff else per_expert
        ssm = 0
        if self.ssm_state:
            di = self.d_inner
            ssm = d * (2 * di + 2 * self.ssm_state + self.ssm_heads) + di * d + di
        per_layer = (att + (moe if self.n_experts else ffn)
                     + (ssm if self.family in ("ssm", "hybrid") else 0))
        if self.family == "ssm":
            per_layer = ssm
        if self.family == "hybrid":
            # mamba layers + one shared attention/ffn block
            return emb + self.n_layers * ssm + (att + ffn)  # shared block counted once
        if self.family == "gdn":
            if self.attn_output_gate:
                att += d * self.n_heads * self.head_dim
            hk, hv, dh = self.lin_k_heads, self.lin_v_heads, self.lin_head_dim
            lin = d * (2 * hk + 2 * hv) * dh + 2 * d * hv + hv * dh * d
            n_full = self.n_full_layers
            return emb + n_full * att + (self.n_layers - n_full) * lin + self.n_layers * moe
        n = self.n_layers + (self.enc_layers if self.family == "encdec" else 0)
        return emb + n * per_layer
