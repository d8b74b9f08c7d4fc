"""Model configuration: a single dataclass covering all assigned families.

``BlockDesc`` describes one layer position inside the repeating pattern
(period): dense / hybrid / ssm / moe architectures are all expressed as a
pattern of (mixer kind, window, moe?) blocks that lax.scan repeats
``n_layers // period`` times -- keeping HLO size depth-independent for the
512-device dry-run compiles (DESIGN.md section 6.2).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any

import jax.numpy as jnp

__all__ = ["BlockDesc", "ModelConfig"]


@dataclass(frozen=True)
class BlockDesc:
    kind: str = "attn"              # "attn" | "mamba"
    window: int | None = None       # sliding-window width for local attention
    moe: bool = False               # MoE MLP instead of dense MLP
    mlp: bool = True                # has an MLP sub-layer at all
    cross_attn: bool = False        # whisper decoder blocks


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    arch_kind: str = "lm"           # lm | encdec | vlm | ssm
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 64
    d_ff: int = 1024
    vocab_size: int = 1024

    # repeating block pattern; len(block_pattern) == period
    block_pattern: tuple[BlockDesc, ...] = (BlockDesc(),)

    # attention details
    rope_theta: float = 10000.0
    qk_norm: bool = False
    attn_softcap: float | None = None
    final_softcap: float | None = None
    rms_eps: float = 1e-6
    act: str = "silu"               # silu | gelu
    causal: bool = True
    tie_embeddings: bool = False
    scale_embed: bool = False       # gemma-style sqrt(d_model) embed scaling
    use_rope: bool = True           # False: no positional encoding (NoPE)
    attn_scale: float | None = None  # query-key scale; None: 1/sqrt(head_dim)
    embedding_multiplier: float = 1.0   # token embeddings times this
    residual_multiplier: float = 1.0    # each sub-block's output times this
    logits_scaling: float = 1.0         # logits divided by this

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_group: int = 1024           # tokens per local-dispatch group
    shared_d_ff: int = 0            # width of an always-on shared expert
    experts_held: int = 0           # experts computed here, of n_experts
    #                                 (expert-parallel share; routing stays
    #                                 over all n_experts, dropless); 0: all
    #                                 experts, capacity-dropping dispatch

    # Mamba-2 (SSD)
    ssm_state: int = 0
    mamba_head_dim: int = 64
    mamba_expand: int = 2
    conv_kernel: int = 4

    # encoder-decoder / frontend stubs
    encoder_layers: int = 0
    encoder_seq: int = 0            # whisper: 1500 precomputed frame embeds
    num_patches: int = 0            # vlm: patch embeddings per image

    # numerics / execution
    dtype: Any = jnp.bfloat16
    use_pallas: bool = False
    remat: str = "full"             # none | full | dots
    logits_chunk: int = 1024        # chunked cross-entropy block
    attn_chunk: int | None = 1024   # XLA-path flash-style q chunk (None =
    #                                 naive full score tensor -- the
    #                                 unoptimized baseline of EXPERIMENTS §Perf)
    scan_layers: bool = True        # lax.scan over layer groups; False
    #                                 unrolls (used by the dry-run's reduced
    #                                 differential configs so cost_analysis
    #                                 sees every layer's FLOPs/collectives)
    vocab_pad_multiple: int = 256   # pad vocab so "model"-axis sharding divides

    # -- derived -------------------------------------------------------------
    @property
    def period(self) -> int:
        return len(self.block_pattern)

    @property
    def n_groups(self) -> int:
        assert self.n_layers % self.period == 0, (
            f"{self.n_layers} layers not divisible by period {self.period}")
        return self.n_layers // self.period

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def mamba_heads(self) -> int:
        return self.mamba_d_inner // self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        # conv runs over concat(x, B, C): d_inner + 2 * ssm_state
        return self.mamba_d_inner + 2 * self.ssm_state

    def replace(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    def has_block(self, kind: str) -> bool:
        return any(b.kind == kind for b in self.block_pattern)

    @property
    def sub_quadratic(self) -> bool:
        """True if decode cost is sub-quadratic in context (ssm / hybrid)."""
        return self.has_block("mamba")
