"""Unified decoder stack: dense / MoE / hybrid / SSM via block patterns.

The stack is ``n_groups = n_layers / period`` repetitions of the config's
``block_pattern`` (a tuple of BlockDesc).  Parameters for one pattern period
are stacked along a leading "layers" axis and the forward pass lax.scans over
groups -- HLO size is O(period), independent of depth (512-device dry-run
compiles stay fast).  gemma2's local/global alternation is period 2; jamba's
1:7 attention:mamba interleave with alternating MoE is period 8; uniform
models are period 1.

Decode carries a per-group cache pytree with the same leading "layers" axis,
scanned jointly with the parameters.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import BlockDesc, ModelConfig
from repro.models.module import ParamSpec, spec_tree_map

__all__ = [
    "stack_specs", "model_specs", "embed_tokens", "forward", "decode_step",
    "init_cache_specs", "unembed",
]

f32 = jnp.float32


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def _block_specs(cfg: ModelConfig, desc: BlockDesc) -> dict:
    sub: dict = {}
    if desc.kind == "attn":
        sub.update(L.spec_attention(cfg))
    elif desc.kind == "mamba":
        sub.update(L.spec_mamba(cfg))
    else:  # pragma: no cover
        raise ValueError(f"unknown block kind {desc.kind}")
    if desc.cross_attn:
        sub.update(L.spec_attention(cfg, prefix="x_"))
    if desc.mlp:
        sub.update(L.spec_moe(cfg) if desc.moe else L.spec_mlp(cfg))
    return sub


def stack_specs(cfg: ModelConfig) -> dict:
    """Per-period block specs, stacked over n_groups on a 'layers' axis."""
    period_specs = {
        f"pos{i}": _block_specs(cfg, d)
        for i, d in enumerate(cfg.block_pattern)
    }
    g = cfg.n_groups

    def stack(s: ParamSpec) -> ParamSpec:
        axes = s.axes if s.axes else tuple(None for _ in s.shape)
        return ParamSpec((g,) + s.shape, s.dtype, ("layers",) + axes, s.init,
                         s.init_scale)

    return spec_tree_map(stack, period_specs)


def model_specs(cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.padded_vocab
    specs: dict = {
        "embed": ParamSpec((v, d), cfg.dtype, ("vocab", "embed"), "normal",
                           0.02),
        "final_norm": ParamSpec((d,), f32, (None,), "zeros"),
        "blocks": stack_specs(cfg),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((d, v), cfg.dtype, ("embed", "vocab"),
                                     "scaled")
    return specs


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array
                 ) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype=x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, dtype=x.dtype)
    return x


def unembed(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    """Hidden states -> (softcapped) logits over the padded vocab."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    z = jnp.einsum("...d,dv->...v", x.astype(f32), head.astype(f32))
    if cfg.logits_scaling != 1.0:
        z = z / cfg.logits_scaling
    if cfg.final_softcap is not None:
        z = cfg.final_softcap * jnp.tanh(z / cfg.final_softcap)
    # mask vocab padding columns
    if cfg.padded_vocab != cfg.vocab_size:
        col = jnp.arange(cfg.padded_vocab)
        z = jnp.where(col < cfg.vocab_size, z, -1e30)
    return z


def _residual(cfg: ModelConfig, x: jax.Array, y: jax.Array) -> jax.Array:
    """A sub-block's output added to the residual stream."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, dtype=y.dtype)
    return x + y


def _feed_forward(cfg: ModelConfig, desc: BlockDesc, p: dict, x: jax.Array,
                  sharder) -> tuple[jax.Array, jax.Array | None]:
    """The block's MLP or MoE sub-block on the residual stream x: (its
    output, the MoE's auxiliary loss or None)."""
    h = L.rmsnorm(x, p["mlp_norm"], cfg.rms_eps)
    if not desc.moe:
        return L.mlp(cfg, p, h, sharder), None
    y, aux = L.moe(cfg, p, h, sharder)
    if cfg.shared_d_ff:
        y = y + L.shared_expert(cfg, p, h)
    return y, aux


def _apply_block(cfg: ModelConfig, desc: BlockDesc, p: dict, x: jax.Array,
                 sharder, positions: jax.Array,
                 enc_out: jax.Array | None, causal: bool) -> tuple:
    aux = jnp.zeros((), f32)
    if desc.kind == "attn":
        h = L.rmsnorm(x, p["norm"], cfg.rms_eps)
        x = _residual(cfg, x, L.attention(cfg, p, h, sharder, desc,
                                          positions, causal=causal))
    else:
        h = L.rmsnorm(x, p["norm"], cfg.rms_eps)
        x = _residual(cfg, x, L.mamba(cfg, p, h, sharder))
    if desc.cross_attn:
        assert enc_out is not None
        h = L.rmsnorm(x, p["x_norm"], cfg.rms_eps)
        x = _residual(cfg, x, L.attention(cfg, p, h, sharder, desc,
                                          positions, xkv=enc_out,
                                          prefix="x_"))
    if desc.mlp:
        y, a = _feed_forward(cfg, desc, p, x, sharder)
        if a is not None:
            aux = aux + a
        x = _residual(cfg, x, y)
    return x, aux


def forward(cfg: ModelConfig, params: dict, x: jax.Array, sharder,
            positions: jax.Array | None = None,
            enc_out: jax.Array | None = None,
            causal: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Run the block stack on embedded inputs x (B, S, d).

    Returns (hidden_states, moe_aux_loss).  ``enc_out`` feeds cross-attention
    blocks (whisper decoder).  ``causal`` overrides cfg.causal (whisper
    encoder passes False).
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    causal = cfg.causal if causal is None else causal

    block_fns = []
    for i, desc in enumerate(cfg.block_pattern):
        def block_fn(x, p, _desc=desc):
            return _apply_block(cfg, _desc, p, x, sharder, positions,
                                enc_out, causal)
        # Per-BLOCK remat: the backward holds one layer's recomputed
        # intermediates at a time (a period-8 jamba group rematted as one
        # unit would keep all 8 layers' internals live simultaneously).
        if cfg.remat == "full":
            block_fn = jax.checkpoint(
                block_fn, policy=jax.checkpoint_policies.nothing_saveable)
        elif cfg.remat == "dots":
            block_fn = jax.checkpoint(
                block_fn, policy=jax.checkpoint_policies.dots_saveable)
        block_fns.append(block_fn)

    def group_body(x, gp):
        aux = jnp.zeros((), f32)
        for i in range(len(cfg.block_pattern)):
            x, a = block_fns[i](x, gp[f"pos{i}"])
            aux = aux + a
        x = sharder.act(x, ("batch", "act_seq", "act_embed"))
        return x, aux

    if cfg.remat in ("full", "dots"):
        # Outer remat keeps the scan backward from saving anything beyond
        # the carry; inner per-block remats bound the recompute live set.
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)

    if cfg.scan_layers:
        def scan_body(carry, gp):
            x, aux = carry
            x, a = group_body(x, gp)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), f32)),
                                   params["blocks"])
    else:
        aux = jnp.zeros((), f32)
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda p: p[g], params["blocks"])
            x, a = group_body(x, gp)
            aux = aux + a
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return x, aux


# ---------------------------------------------------------------------------
# decode (one token against per-group caches)
# ---------------------------------------------------------------------------

def init_cache_specs(cfg: ModelConfig, batch: int, max_seq: int,
                     cross_seq: int = 0) -> dict:
    """ShapeDtypeStruct/ParamSpec tree for the decode cache.

    Self-attention blocks carry (B, S, KV, dh) k/v; mamba blocks carry conv
    (B, K-1, di+2n) + ssm (B, Hm, n, dh) states; cross-attention blocks carry
    static (B, S_enc, KV, dh) k/v computed at prefill.
    """
    g = cfg.n_groups
    cache: dict = {}
    for i, desc in enumerate(cfg.block_pattern):
        sub: dict = {}
        if desc.kind == "attn":
            kv = (g, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
            axes = ("layers", "cache_batch", "cache_seq", "cache_heads", None)
            sub["k"] = ParamSpec(kv, cfg.dtype, axes, "zeros")
            sub["v"] = ParamSpec(kv, cfg.dtype, axes, "zeros")
        else:
            sub["conv"] = ParamSpec(
                (g, batch, cfg.conv_kernel - 1, cfg.mamba_conv_dim),
                cfg.dtype, ("layers", "cache_batch", None, "mamba_inner"),
                "zeros")
            sub["ssm"] = ParamSpec(
                (g, batch, cfg.mamba_heads, cfg.ssm_state, cfg.mamba_head_dim),
                f32, ("layers", "cache_batch", "mamba_heads", None, None),
                "zeros")
        if desc.cross_attn:
            xkv = (g, batch, cross_seq, cfg.n_kv_heads, cfg.head_dim)
            axes = ("layers", "cache_batch", None, "cache_heads", None)
            sub["xk"] = ParamSpec(xkv, cfg.dtype, axes, "zeros")
            sub["xv"] = ParamSpec(xkv, cfg.dtype, axes, "zeros")
        cache[f"pos{i}"] = sub
    return cache


def decode_step(cfg: ModelConfig, params: dict, token: jax.Array,
                pos: jax.Array, cache: dict, sharder
                ) -> tuple[jax.Array, dict]:
    """One decode step: token (B,), pos (B,) -> (logits (B, V), new cache)."""
    x = embed_tokens(cfg, params, token[:, None])             # (B,1,d)

    def group_body(x, scanned):
        gp, gc = scanned
        newc = {}
        for i, desc in enumerate(cfg.block_pattern):
            p, c = gp[f"pos{i}"], gc[f"pos{i}"]
            nc = {}
            if desc.kind == "attn":
                h = L.rmsnorm(x, p["norm"], cfg.rms_eps)
                y, nc["k"], nc["v"] = L.attention_decode(
                    cfg, p, h, sharder, desc, pos, c["k"], c["v"])
                x = _residual(cfg, x, y)
            else:
                h = L.rmsnorm(x, p["norm"], cfg.rms_eps)
                y, nc["conv"], nc["ssm"] = L.mamba_decode(
                    cfg, p, h, c["conv"], c["ssm"])
                x = _residual(cfg, x, y)
            if desc.cross_attn:
                h = L.rmsnorm(x, p["x_norm"], cfg.rms_eps)
                y, nc["xk"], nc["xv"] = L.attention_decode(
                    cfg, p, h, sharder, desc, pos, c["xk"], c["xv"],
                    cross=True, prefix="x_")
                x = _residual(cfg, x, y)
            if desc.mlp:
                y, _ = _feed_forward(cfg, desc, p, x, sharder)
                x = _residual(cfg, x, y)
            newc[f"pos{i}"] = nc
        return x, newc

    if cfg.scan_layers:
        (x, new_cache) = jax.lax.scan(
            lambda carry, scanned: group_body(carry, scanned),
            x, (params["blocks"], cache))
    else:
        parts = []
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda p: p[g], params["blocks"])
            gc = jax.tree.map(lambda c: c[g], cache)
            x, nc = group_body(x, (gp, gc))
            parts.append(nc)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_eps)
    logits = unembed(cfg, params, x[:, 0])                    # (B, V)
    return logits, new_cache
