"""Plain reference of Granite 4.0-H (``granitemoehybrid``), with its seeded
weights and the work counts of its cell.

The model: token embeddings times ``embedding_multiplier``; a stack of
pre-norm layers, each a mixer -- the Mamba-2 SSD mixer or grouped-query
attention with no positional encoding at scale ``attention_multiplier``
-- and an MoE block, each sub-block's output times
``residual_multiplier`` before it joins the residual stream; a final RMS
norm; the output head tied to the embedding, its logits divided by
``logits_scaling``.  The MoE routes over all the published experts: f32
logits, the top ``num_experts_per_tok``, gates a softmax over those; each
chosen expert is a SwiGLU of width ``intermediate_size``; one shared
SwiGLU expert of width ``shared_intermediate_size`` is added for every
token (IBM's Granite 4.0 release; the equations of ``transformers``'
``modeling_granitemoehybrid.py``).

Cut to one chip's share as the configuration file states: its first
``num_hidden_layers`` layers, and of the router's
``published["num_local_experts"]`` experts only experts 0 ..
``num_local_experts - 1`` are computed.  What the other experts would
add is left out here as in the program.

The reference follows the program's parameter layout.  Departures: the
RMS-norm weights are stored as offsets from 1; the Mamba-2 mixer is
``mamba2``'s reference (the recurrence step by step, one group), where
the published code runs a chunked scan (``mamba_chunk_size`` 256) of the
same recurrence; attention is ``dense_lm``'s, one block of queries at a
time, with the queries pre-multiplied so that its ``1/sqrt(head_dim)``
becomes ``attention_multiplier``; the router's logits are computed from
the normalized activations in f32, where the published code rounds its
product to the model's dtype before widening it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import work
from bench.models import dense_lm, mamba2
from bench.models.common import (draw_weights, einsum, f32, normal,
                                 operand_rounding, rms_norm)
from bench.work import Work, logit_width

# Output head scale: logits of std ~2 over the vocabulary after the
# published division by ``logits_scaling`` (hidden 4096: embedding std 0.5,
# embedded tokens of RMS 6 after the published multiplier 12).
LOGIT_STD = 2.0
# Attention scores of std ~3 at the published scale, so each query attends
# to a few keys and the causal mask decides what it sees.
SCORE_STD = 3.0
# Router logits of std ~2: each token's top 10 stand apart from the rest.
ROUTER_STD = 2.0
# Ragged expert matmuls per MoE layer: gate, up and down projections.
EXPERT_LAUNCHES = 3


def _experts(c: dict) -> tuple[int, int, int]:
    """(experts the router chooses from, experts held here, top-k)."""
    return (c["published"]["num_local_experts"], c["num_local_experts"],
            c["num_experts_per_tok"])


def _kinds(c: dict) -> list[str]:
    return c["layer_types"][:c["num_hidden_layers"]]


def program_fields(c: dict) -> dict:
    """The program's ``ModelConfig`` fields this configuration fixes."""
    from repro.models.config import BlockDesc

    routed, held, k = _experts(c)
    return {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "block_pattern": tuple(
            BlockDesc(kind="attn" if kind == "attention" else "mamba",
                      moe=True) for kind in _kinds(c)),
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "use_rope": c["position_embedding_type"] == "rope",
        "attn_scale": c["attention_multiplier"],
        "embedding_multiplier": c["embedding_multiplier"],
        "residual_multiplier": c["residual_multiplier"],
        "logits_scaling": c["logits_scaling"],
        "n_experts": routed, "experts_held": held, "top_k": k,
        "moe_d_ff": c["intermediate_size"],
        "shared_d_ff": c["shared_intermediate_size"],
        "ssm_state": c["mamba_d_state"], "mamba_head_dim": c["mamba_d_head"],
        "mamba_expand": c["mamba_expand"], "mamba_heads": c["mamba_n_heads"],
        "conv_kernel": c["mamba_d_conv"], "vocab_size": c["vocab_size"],
        "rms_eps": c["rms_norm_eps"],
        "tie_embeddings": c["tie_word_embeddings"], "act": c["hidden_act"],
    }


def _mamba2_config(c: dict) -> dict:
    """The keys under which ``mamba2``'s reference reads the mixer."""
    return {"d_model": c["hidden_size"], "expand": c["mamba_expand"],
            "headdim": c["mamba_d_head"], "d_state": c["mamba_d_state"],
            "d_conv": c["mamba_d_conv"], "rms_norm_eps": c["rms_norm_eps"]}


def init_weights(c: dict, abstract, key: jax.Array) -> dict:
    """Seeded weights that keep the residual stream well conditioned under
    the published multipliers: token embeddings of RMS 6 once multiplied,
    and each sub-block's output projection scaled so that, times the
    residual multiplier, it adds 6/sqrt(2 * layers) (GPT-2's rule).
    Mamba-2's published initialization for A (A = -U[1, 16]), dt
    (log-uniform in [1e-3, 1e-1] through softplus) and D (ones), as
    ``mamba2``'s reference draws them."""
    d, dh = c["hidden_size"], c["head_dim"]
    embed_std = LOGIT_STD * c["logits_scaling"] / d ** 0.5
    stream = embed_std * c["embedding_multiplier"]
    out = stream / (c["residual_multiplier"]
                    * (2 * c["num_hidden_layers"]) ** 0.5)
    qk = (SCORE_STD / (c["attention_multiplier"] * dh ** 0.5)) ** 0.5
    d_inner = c["mamba_expand"] * d
    std = {
        "embed": embed_std, "router": ROUTER_STD / d ** 0.5,
        "wq": qk / d ** 0.5, "wk": qk / d ** 0.5, "wv": d ** -0.5,
        "wo": out / (c["num_attention_heads"] * dh) ** 0.5,
        "out_proj": out / d_inner ** 0.5,
        "we_gate": d ** -0.5, "we_up": d ** -0.5,
        "we_down": out / c["intermediate_size"] ** 0.5,
        "ws_gate": d ** -0.5, "ws_up": d ** -0.5,
        "ws_down": out / c["shared_intermediate_size"] ** 0.5,
    }
    k_conv = c["mamba_d_conv"]

    def rule(name, shape, k):
        if name in std:
            return normal(k, shape, std[name])
        if name == "in_proj":
            return normal(k, shape, d ** -0.5)
        if name == "conv_w":
            bound = k_conv ** -0.5
            return jax.random.uniform(k, shape, f32, -bound, bound)
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, f32, jnp.log(1e-3), jnp.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
        if name == "D":
            return jnp.ones(shape, f32)
        if name == "conv_b" or name.endswith("norm"):
            return jnp.zeros(shape, f32)
        raise KeyError(f"no initializer for parameter {name!r}")

    return draw_weights(abstract, key, rule)


def _attention(c: dict, p: dict, h: jax.Array, rnd, q_block: int
               ) -> jax.Array:
    R, S, _ = h.shape
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    q = einsum("rsd,de->rse", h, p["wq"], rnd).reshape(R, S, H, dh)
    k = einsum("rsd,de->rse", h, p["wk"], rnd).reshape(R, S, KV, dh)
    v = einsum("rsd,de->rse", h, p["wv"], rnd).reshape(R, S, KV, dh)
    q = q * (c["attention_multiplier"] * dh ** 0.5)
    o = dense_lm._attention(q, k, v, rnd, q_block)
    return einsum("rse,ed->rsd", o, p["wo"], rnd)


def _swiglu(h, w_gate, w_up, w_down, rnd) -> jax.Array:
    g = jax.nn.silu(einsum("rsd,df->rsf", h, w_gate, rnd))
    u = einsum("rsd,df->rsf", h, w_up, rnd)
    return einsum("rsf,fd->rsd", g * u, w_down, rnd)


def _moe(c: dict, p: dict, h: jax.Array, rnd) -> jax.Array:
    """Each token's held experts among its top k, weighted by the softmax
    of its top-k router logits, plus the shared expert."""
    _, held, k = _experts(c)
    logits = einsum("rsd,de->rse", h, p["router"], rnd)
    top, chosen = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)                      # (R, S, k)
    out = _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], rnd)
    for e in range(held):
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        out = out + weight[..., None] * _swiglu(
            h, p["we_gate"][e], p["we_up"][e], p["we_down"][e], rnd)
    return out


def reference_logits(c: dict, w: dict, tokens: jax.Array,
                     positions: jax.Array, quant: str | None = None,
                     q_block: int = 256) -> jax.Array:
    """Logits (R, P, vocab) at ``positions`` of token rows (R, S), from
    the full forward over each row."""
    rnd = operand_rounding(quant)
    eps, mult = c["rms_norm_eps"], c["residual_multiplier"]
    period = len(w["blocks"])
    kinds = _kinds(c)
    if kinds != kinds[:period] * (len(kinds) // period):
        raise ValueError(f"layer_types do not repeat every {period} layers")
    mixer_c = _mamba2_config(c)
    x = w["embed"][tokens].astype(f32) * c["embedding_multiplier"]

    def group(x, gp):
        for i, kind in enumerate(kinds[:period]):
            p = gp[f"pos{i}"]
            h = rms_norm(x, p["norm"], eps)
            if kind == "attention":
                y = _attention(c, p, h, rnd, q_block)
            else:
                y = mamba2._mixer(mixer_c, p, h, rnd)
            x = x + mult * y
            x = x + mult * _moe(c, p, rms_norm(x, p["mlp_norm"], eps), rnd)
        return x, None

    x, _ = jax.lax.scan(group, x, w["blocks"])
    x = rms_norm(x[:, positions], w["final_norm"], eps)
    logits = einsum("rpd,vd->rpv", x, w["embed"], rnd) / c["logits_scaling"]
    return logits[..., :logit_width(c)]


# -- the work the cell's metrics count ----------------------------------------

def expected_rows(c: dict, tokens: int) -> float:
    """(token, expert) pairs routed to the held experts in a layer, on
    average: each token's k picks spread over the router's experts."""
    routed, held, k = _experts(c)
    return tokens * k * held / routed


def kernel_calls(c: dict, batch: int, seq: int) -> dict[str, Work]:
    """Needed work of one call of each kernel the cell's metrics read, in
    a full-sequence forward of (batch, seq) tokens: the SSD scan (one call
    per Mamba layer), and the held experts' three matmuls of one MoE
    layer over the expected rows, the weights and those rows moved once
    (``EXPERT_LAUNCHES`` calls per layer)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    _, held, _ = _experts(c)
    rows = expected_rows(c, batch * seq)
    experts = Work(3 * 2 * d * f * rows,
                   2 * (3 * held * d * f + 2 * rows * d))
    return {"ssd_scan": work.ssd_scan(
                batch, c["mamba_n_heads"], seq, c["mamba_d_head"],
                c["mamba_d_state"]),
            "moe_experts": experts}


def forward_flops(c: dict, batch: int, seq: int, logit_positions: int
                  ) -> float:
    """FLOPs one forward step needs: 2 per matmul parameter per token for
    the mixers' projections, the router and the shared expert, the held
    experts over their expected rows, the SSD recurrence, causal
    attention, and the unembedding of ``logit_positions`` positions per
    row."""
    tokens = batch * seq
    d, H, KV, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    routed, _, _ = _experts(c)
    d_inner, heads, n = (c["mamba_expand"] * d, c["mamba_n_heads"],
                         c["mamba_d_state"])
    kinds = _kinds(c)
    mambas, attns = kinds.count("mamba"), kinds.count("attention")
    layers = mambas + attns
    mamba_params = d * (2 * d_inner + 2 * n + heads) + d_inner * d
    attn_params = d * (H + 2 * KV) * dh + H * dh * d
    dense_params = d * routed + 3 * d * c["shared_intermediate_size"]
    matmul = (mambas * mamba_params + attns * attn_params
              + layers * dense_params)
    calls = kernel_calls(c, batch, seq)
    experts = layers * calls["moe_experts"].flops
    ssd = mambas * calls["ssd_scan"].flops
    attention = attns * work.flash_attention(batch, H, KV, seq, dh).flops
    unembed = 2 * d * logit_width(c) * batch * logit_positions
    return 2 * matmul * tokens + experts + ssd + attention + unembed
