"""Plain reference of a dense decoder-only language model, with its seeded
weights: pre-norm blocks of grouped-query attention with rotary position
embedding and a SwiGLU MLP, a final RMS norm and an untied output head
(InternLM2, arXiv:2403.17297; the Llama block).

The reference follows the published description and the layout of the
program's parameters.  Departures: the RMS-norm weights are stored as
offsets from 1 (the program's layout), and the rotary embedding rotates
the two halves of each head (the Hugging Face ``rotate_half`` form).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models.common import (draw_weights, einsum, f32, normal,
                                 operand_rounding, rms_norm)
from bench.work import logit_width

# Query/key scale of the seeded weights: scores of std ~3, so each query
# attends to a few keys and the causal mask decides what it sees.
QK_STD = 1.7
# Output head scale: logits of std ~2 over the vocabulary.
LOGIT_STD = 2.0


def program_fields(c: dict) -> dict:
    """The program's ``ModelConfig`` fields this configuration fixes."""
    return {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
        "rope_theta": c["rope_theta"], "rms_eps": c["rms_norm_eps"],
        "tie_embeddings": c["tie_word_embeddings"], "act": "silu",
    }


def init_weights(c: dict, abstract, key: jax.Array) -> dict:
    """Seeded weights that keep the residual stream well conditioned, as a
    trained model's is: token embeddings of unit scale, and each block's
    output projections scaled by 1/sqrt(2 * layers) (GPT-2's rule), so
    rounding is not amplified layer after layer."""
    d, dh = c["hidden_size"], c["head_dim"]
    q_dim = c["num_attention_heads"] * dh
    depth = (2 * c["num_hidden_layers"]) ** 0.5
    std = {
        "embed": 1.0, "lm_head": LOGIT_STD / d ** 0.5,
        "wq": QK_STD / d ** 0.5, "wk": QK_STD / d ** 0.5,
        "wv": d ** -0.5, "wo": 1 / (q_dim ** 0.5 * depth),
        "w_gate": d ** -0.5, "w_up": d ** -0.5,
        "w_down": 1 / (c["intermediate_size"] ** 0.5 * depth),
    }

    def rule(name, shape, k):
        if name in std:
            return normal(k, shape, std[name])
        if name.endswith("norm"):
            return jnp.zeros(shape, f32)
        raise KeyError(f"no initializer for parameter {name!r}")

    return draw_weights(abstract, key, rule)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (R, S, H, dh) at positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(x.shape[1], dtype=f32)[:, None] * freqs   # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, rnd, q_block: int):
    """Causal grouped-query attention, q (R, S, H, dh), k/v (R, S, KV, dh),
    one block of queries at a time so the scores fit."""
    R, S, H, dh = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    qb = min(q_block, S)
    blocks = q.reshape(R, S // qb, qb, H, dh).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(S)

    def one(args):
        i, qblk = args
        s = einsum("rqhd,rkhd->rhqk", qblk, k, rnd) * dh ** -0.5
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return einsum("rhqk,rkhd->rqhd", p, v, rnd)

    out = jax.lax.map(one, (jnp.arange(S // qb), blocks))
    return out.transpose(1, 0, 2, 3, 4).reshape(R, S, H * dh)


def reference_logits(c: dict, w: dict, tokens: jax.Array,
                     positions: jax.Array, quant: str | None = None,
                     q_block: int = 512) -> jax.Array:
    """Logits (R, P, vocab) at ``positions`` of token rows (R, S), from
    the full forward over each row."""
    rnd = operand_rounding(quant)
    eps = c["rms_norm_eps"]
    R, S = tokens.shape
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    x = w["embed"][tokens].astype(f32)

    def layer(x, p):
        h = rms_norm(x, p["norm"], eps)
        q = einsum("rsd,de->rse", h, p["wq"], rnd).reshape(R, S, H, dh)
        k = einsum("rsd,de->rse", h, p["wk"], rnd).reshape(R, S, KV, dh)
        v = einsum("rsd,de->rse", h, p["wv"], rnd).reshape(R, S, KV, dh)
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
        x = x + einsum("rse,ed->rsd", _attention(q, k, v, rnd, q_block),
                       p["wo"], rnd)
        h = rms_norm(x, p["mlp_norm"], eps)
        g = jax.nn.silu(einsum("rsd,df->rsf", h, p["w_gate"], rnd))
        u = einsum("rsd,df->rsf", h, p["w_up"], rnd)
        return x + einsum("rsf,fd->rsd", g * u, p["w_down"], rnd), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["pos0"])
    x = rms_norm(x[:, positions], w["final_norm"], eps)
    logits = einsum("rpd,dv->rpv", x, w["lm_head"], rnd)
    return logits[..., :logit_width(c)]
