"""Plain reference of a Mamba-2 language model, with its seeded weights:
pre-norm blocks of the SSD mixer (arXiv:2405.21060) -- one input
projection to (z, x, B, C, dt), a causal depthwise convolution over
(x, B, C), the selective state-space recurrence run step by step, a skip
through D, gating by silu(z), an RMS norm and the output projection --
then a final RMS norm and the output head tied to the embedding.

The recurrence is the definition, one position after another:
    h_t = exp(A dt_t) h_{t-1} + dt_t B_t x_t^T,   y_t = C_t h_t + D x_t
with one group (B and C shared by every head).  Departure: the RMS-norm
weights are stored as offsets from 1, the program's layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models.common import (draw_weights, einsum, f32, normal,
                                 operand_rounding, rms_norm)
from bench.work import logit_width

# Tied embedding scale: logits of std ~2.8 at d_model 768.
EMBED_STD = 0.1


def program_fields(c: dict) -> dict:
    """The program's ``ModelConfig`` fields this configuration fixes."""
    return {
        "n_layers": c["n_layer"], "d_model": c["d_model"],
        "ssm_state": c["d_state"], "mamba_head_dim": c["headdim"],
        "mamba_expand": c["expand"], "conv_kernel": c["d_conv"],
        "vocab_size": logit_width(c), "rms_eps": c["rms_norm_eps"],
        "tie_embeddings": c["tie_embeddings"],
    }


def _dims(c: dict):
    d_inner = c["expand"] * c["d_model"]
    return d_inner, d_inner // c["headdim"], c["headdim"], c["d_state"]


def init_weights(c: dict, abstract, key: jax.Array) -> dict:
    """Mamba-2's published initialization for A (A = -U[1, 16]), dt
    (log-uniform in [1e-3, 1e-1] through softplus) and D (ones); unit-fan-in
    projections with the output projection scaled by 1/sqrt(2 * layers)."""
    d_inner = _dims(c)[0]
    depth = (2 * c["n_layer"]) ** 0.5
    k_conv = c["d_conv"]

    def rule(name, shape, k):
        if name == "embed":
            return normal(k, shape, EMBED_STD)
        if name == "in_proj":
            return normal(k, shape, c["d_model"] ** -0.5)
        if name == "out_proj":
            return normal(k, shape, 1 / (d_inner ** 0.5 * depth))
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


def _mixer(c: dict, p: dict, h: jax.Array, rnd) -> jax.Array:
    R, S, _ = h.shape
    d_inner, H, dh, n = _dims(c)
    proj = einsum("rsd,de->rse", h, p["in_proj"], rnd)
    z, xin, B, C, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + n, 2 * d_inner + 2 * n],
        axis=-1)
    xbc = jnp.concatenate([xin, B, C], axis=-1)
    K = c["d_conv"]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * p["conv_w"][i].astype(f32)
               for i in range(K)) + p["conv_b"]
    xin, B, C = jnp.split(jax.nn.silu(conv), [d_inner, d_inner + n], -1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (R, S, H)
    A = -jnp.exp(p["A_log"])                                # (H,)
    x = rnd(xin).reshape(R, S, H, dh)
    B, C = rnd(B), rnd(C)

    def step(state, inp):                                   # (R, H, n, dh)
        x_t, B_t, C_t, dt_t = inp
        state = (jnp.exp(A * dt_t)[..., None, None] * state
                 + dt_t[..., None, None] * B_t[:, None, :, None]
                 * x_t[:, :, None, :])
        return state, jnp.einsum("rn,rhnd->rhd", C_t, state,
                                 precision=jax.lax.Precision.HIGHEST)

    time_major = [a.swapaxes(0, 1) for a in (x, B, C, dt)]
    state0 = jnp.zeros((R, H, n, dh), f32)
    _, y = jax.lax.scan(step, state0, time_major, unroll=8)
    y = y.swapaxes(0, 1) + p["D"][:, None] * x              # (R, S, H, dh)
    y = y.reshape(R, S, d_inner) * jax.nn.silu(z)
    y = rms_norm(y, p["ssm_norm"], c["rms_norm_eps"])
    return einsum("rse,ed->rsd", y, p["out_proj"], rnd)


def reference_logits(c: dict, w: dict, tokens: jax.Array,
                     positions: jax.Array, quant: str | None = None
                     ) -> jax.Array:
    """Logits (R, P, width of the output head) at ``positions`` of token
    rows (R, S)."""
    rnd = operand_rounding(quant)
    x = w["embed"][tokens].astype(f32)

    def layer(x, p):
        h = rms_norm(x, p["norm"], c["rms_norm_eps"])
        return x + _mixer(c, p, h, rnd), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["pos0"])
    x = rms_norm(x[:, positions], w["final_norm"], c["rms_norm_eps"])
    logits = einsum("rpd,vd->rpv", x, w["embed"], rnd)
    return logits[..., :logit_width(c)]
