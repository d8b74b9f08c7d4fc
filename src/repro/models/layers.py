"""Model layers: norms, RoPE, GQA attention, MLP, MoE, Mamba-2 (SSD).

All layers are pure functions (cfg, params, x, ...) -> y over plain dict
pytrees; ``spec_*`` functions give the matching ParamSpec trees with logical
sharding axes.  Kernel-heavy paths route through repro.kernels.ops so the
KLARAPTOR driver picks Pallas launch parameters when enabled; the default
(use_pallas=False) path is pure XLA and is what the multi-pod dry-run lowers.

The train-time SSD path is deliberately scan-free (chunk-parallel +
log-depth associative scan over chunk states): XLA's cost model counts while
-loop bodies only once, so a sequential scan would make the roofline analysis
blind to the recurrence FLOPs.  The chunk-parallel form is also the
TPU-native formulation (everything is an MXU matmul).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.config import BlockDesc, ModelConfig
from repro.models.module import ParamSpec

__all__ = [
    "rmsnorm", "rope", "spec_attention", "attention", "attention_decode",
    "spec_mlp", "mlp", "spec_moe", "moe", "moe_held", "shared_expert",
    "spec_mamba", "mamba",
    "mamba_decode", "ssd_parallel",
]

f32 = jnp.float32


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(f32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * (1.0 + w.astype(f32))
            ).astype(x.dtype)


def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=f32) / half))


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (..., S, H, dh); positions: (..., S)."""
    half = x.shape[-1] // 2
    freqs = _rope_freqs(x.shape[-1], theta)                      # (half,)
    angles = positions[..., :, None].astype(f32) * freqs         # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                          # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(f32), x[..., half:].astype(f32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA + window + softcap + qk-norm; self and cross)
# ---------------------------------------------------------------------------

def spec_attention(cfg: ModelConfig, prefix: str = "") -> dict:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        f"{prefix}norm": ParamSpec((d,), f32, (None,), "zeros"),
        f"{prefix}wq": ParamSpec((d, qd), cfg.dtype, ("embed", "heads"),
                                 "scaled"),
        f"{prefix}wk": ParamSpec((d, kvd), cfg.dtype, ("embed", "kv_heads"),
                                 "scaled"),
        f"{prefix}wv": ParamSpec((d, kvd), cfg.dtype, ("embed", "kv_heads"),
                                 "scaled"),
        f"{prefix}wo": ParamSpec((qd, d), cfg.dtype, ("heads", "embed"),
                                 "scaled"),
    }
    if cfg.qk_norm:
        p[f"{prefix}q_norm"] = ParamSpec((cfg.head_dim,), f32, (None,), "zeros")
        p[f"{prefix}k_norm"] = ParamSpec((cfg.head_dim,), f32, (None,), "zeros")
    return p


def _project_qkv(cfg: ModelConfig, p: dict, xq: jax.Array, xkv: jax.Array,
                 prefix: str = ""):
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    q = (xq @ p[f"{prefix}wq"]).reshape(B, Sq, cfg.n_heads, cfg.head_dim)
    k = (xkv @ p[f"{prefix}wk"]).reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    v = (xkv @ p[f"{prefix}wv"]).reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm and f"{prefix}q_norm" in p:
        q = rmsnorm(q, p[f"{prefix}q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p[f"{prefix}k_norm"], cfg.rms_eps)
    return q, k, v


def attention(cfg: ModelConfig, p: dict, xq: jax.Array, sharder,
              desc: BlockDesc, positions: jax.Array,
              xkv: jax.Array | None = None, causal: bool | None = None,
              prefix: str = "") -> jax.Array:
    """Full-sequence attention (training / prefill).  Self unless xkv given."""
    cross = xkv is not None
    xkv = xq if xkv is None else xkv
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    q, k, v = _project_qkv(cfg, p, xq, xkv, prefix)
    if not cross and cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = sharder.act(q, ("batch", "act_seq", "act_heads", None))
    k = sharder.act(k, ("batch", "act_seq", "act_heads", None))
    causal = cfg.causal if causal is None else causal
    causal = causal and not cross
    # flatten heads for the kernel interface: (B*H, S, dh)
    qf = q.transpose(0, 2, 1, 3).reshape(B * cfg.n_heads, Sq, cfg.head_dim)
    kf = k.transpose(0, 2, 1, 3).reshape(B * cfg.n_kv_heads, Skv, cfg.head_dim)
    vf = v.transpose(0, 2, 1, 3).reshape(B * cfg.n_kv_heads, Skv, cfg.head_dim)
    out = ops.flash_attention(
        qf, kf, vf, num_q_heads=cfg.n_heads, num_kv_heads=cfg.n_kv_heads,
        causal=causal, window=desc.window, softcap=cfg.attn_softcap,
        scale=cfg.attn_scale, use_pallas=cfg.use_pallas,
        q_chunk=cfg.attn_chunk)
    out = out.reshape(B, cfg.n_heads, Sq, cfg.head_dim).transpose(0, 2, 1, 3)
    out = out.reshape(B, Sq, cfg.q_dim)
    return out @ p[f"{prefix}wo"]


def attention_decode(cfg: ModelConfig, p: dict, x1: jax.Array, sharder,
                     desc: BlockDesc, pos: jax.Array,
                     cache_k: jax.Array, cache_v: jax.Array, layer,
                     cross: bool = False, prefix: str = ""):
    """One-token decode against layer ``layer`` of a stacked
    (G, B, S_cache, KV, dh) KV cache.

    For self-attention the new token's k/v are written in place at
    ``(layer, b, pos[b])`` before the layer's slice is read; for
    cross-attention the cache is static (encoder outputs).  Returns
    (y, cache_k, cache_v), the caches still stacked.
    """
    B = x1.shape[0]
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = H // KV
    S = cache_k.shape[2]

    q = (x1 @ p[f"{prefix}wq"]).reshape(B, 1, H, dh)
    if cfg.qk_norm and f"{prefix}q_norm" in p:
        q = rmsnorm(q, p[f"{prefix}q_norm"], cfg.rms_eps)
    if not cross:
        k1 = (x1 @ p[f"{prefix}wk"]).reshape(B, 1, KV, dh)
        v1 = (x1 @ p[f"{prefix}wv"]).reshape(B, 1, KV, dh)
        if cfg.qk_norm and f"{prefix}k_norm" in p:
            k1 = rmsnorm(k1, p[f"{prefix}k_norm"], cfg.rms_eps)
        if cfg.use_rope:
            q = rope(q, pos[:, None], cfg.rope_theta)
            k1 = rope(k1, pos[:, None], cfg.rope_theta)
        cache_k = _write_cache(cache_k, k1, pos, layer)
        cache_v = _write_cache(cache_v, v1, pos, layer)
    ck, cv = cache_k[layer], cache_v[layer]

    # Keep the cache in its storage dtype: upcasting (B, S, KV, dh) to f32
    # would materialize a second full cache; accumulate in f32 instead.
    qf = q.reshape(B, KV, group, dh).astype(ck.dtype)
    scale = cfg.attn_scale if cfg.attn_scale is not None else dh ** -0.5
    s = jnp.einsum("bkgd,bskd->bkgs", qf, ck,
                   preferred_element_type=f32) * scale     # (B, KV, g, S)
    if cfg.attn_softcap is not None:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    kpos = jnp.arange(S)[None, None, None, :]
    mask = kpos <= pos[:, None, None, None]
    if desc.window is not None and not cross:
        mask &= kpos > (pos[:, None, None, None] - desc.window)
    if cross:
        mask = jnp.ones_like(mask)
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w.astype(cv.dtype), cv,
                     preferred_element_type=f32)           # (B, KV, g, dh)
    out = out.reshape(B, 1, H * dh).astype(x1.dtype)
    return out @ p[f"{prefix}wo"], cache_k, cache_v


def _write_cache(cache: jax.Array, new: jax.Array, pos: jax.Array, layer
                 ) -> jax.Array:
    """Store (B, 1, KV, dh) ``new`` at ``(layer, b, pos[b])`` of the
    stacked (G, B, S, KV, dh) cache: one row per slot, every other row
    untouched.  A ``pos`` outside [0, S) writes nothing."""
    B, S = new.shape[0], cache.shape[2]
    # An index of S is dropped by the scatter; a negative one would wrap.
    row = jnp.where((pos >= 0) & (pos < S), pos, S)
    return cache.at[layer, jnp.arange(B), row].set(
        new[:, 0].astype(cache.dtype), mode="drop")


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def spec_mlp(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mlp_norm": ParamSpec((d,), f32, (None,), "zeros"),
        "w_gate": ParamSpec((d, f), cfg.dtype, ("embed", "mlp"), "scaled"),
        "w_up": ParamSpec((d, f), cfg.dtype, ("embed", "mlp"), "scaled"),
        "w_down": ParamSpec((f, d), cfg.dtype, ("mlp", "embed"), "scaled"),
    }


def _act(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


def mlp(cfg: ModelConfig, p: dict, x: jax.Array, sharder) -> jax.Array:
    h = _act(cfg, x @ p["w_gate"]) * (x @ p["w_up"])
    h = sharder.act(h, ("batch", "act_seq", "act_mlp"))
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE MLP (top-k router, sort-based capacity dispatch, EP-shardable)
# ---------------------------------------------------------------------------

def spec_moe(cfg: ModelConfig) -> dict:
    """Router over all ``n_experts``; weights of the experts held here;
    the shared expert's where the config has one."""
    d, f, E = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts
    held = cfg.experts_held or E
    p = {
        "mlp_norm": ParamSpec((d,), f32, (None,), "zeros"),
        "router": ParamSpec((d, E), f32, ("embed", None), "scaled"),
        "we_gate": ParamSpec((held, d, f), cfg.dtype,
                             ("experts", "embed", "mlp"), "scaled"),
        "we_up": ParamSpec((held, d, f), cfg.dtype,
                           ("experts", "embed", "mlp"), "scaled"),
        "we_down": ParamSpec((held, f, d), cfg.dtype,
                             ("experts", "mlp", "embed"), "scaled"),
    }
    if cfg.shared_d_ff:
        fs = cfg.shared_d_ff
        p.update({
            "ws_gate": ParamSpec((d, fs), cfg.dtype, ("embed", "mlp"),
                                 "scaled"),
            "ws_up": ParamSpec((d, fs), cfg.dtype, ("embed", "mlp"),
                               "scaled"),
            "ws_down": ParamSpec((fs, d), cfg.dtype, ("mlp", "embed"),
                                 "scaled"),
        })
    return p


def shared_expert(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """The expert every token passes through, beside the routed ones."""
    return (_act(cfg, x @ p["ws_gate"]) * (x @ p["ws_up"])) @ p["ws_down"]


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    cap = math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor
                    / cfg.n_experts)
    return max(8, ((cap + 7) // 8) * 8)


def moe(cfg: ModelConfig, p: dict, x: jax.Array, sharder,
        first_expert: int = 0) -> tuple[jax.Array, jax.Array]:
    """Top-k MoE: grouped local dispatch + expert-sharded einsum (GShard
    groups, sort-based slots).  With ``cfg.experts_held`` set, the
    dropless part of experts ``first_expert`` onwards instead
    (``moe_held``).

    Tokens are split into groups of ``moe_group``; each group sorts its own
    (token, expert) pairs and scatters into capacity-padded slots.  The
    sort/gather/scatter are vmapped over the group axis, so under SPMD they
    are *batched* ops sharded on groups (data axis) -- no token tensor is
    ever replicated (a global sort would be: data-dependent gathers don't
    partition).  The expert FFN is a single einsum with the expert axis
    sharded over "model" on both the slot buffer and the weights (EP).
    Dropped tokens (over capacity) fall back to the residual, Switch-style.

    Returns (y, aux_loss).
    """
    if cfg.experts_held:
        return moe_held(cfg, p, x, first_expert)
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    g_size = min(cfg.moe_group, T)
    assert T % g_size == 0, (T, g_size)
    G = T // g_size
    C = moe_capacity(cfg, g_size)
    xg = x.reshape(G, g_size, d)
    xg = sharder.act(xg, ("moe_groups", None, "moe_token_d"))

    logits = jnp.einsum("gtd,de->gte", xg, p["router"].astype(x.dtype),
                        preferred_element_type=f32)          # (G, g, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, k)                   # (G, g, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    def dispatch_one(xr, er, gr):
        """One group: (g, d), (g, k), (g, k) -> slot buffer + combine meta."""
        gk = g_size * k
        flat_e = er.reshape(gk)
        flat_g = gr.reshape(gk)
        tok = jnp.arange(gk, dtype=jnp.int32) // k
        order = jnp.argsort(flat_e, stable=True)
        se, st, sg = flat_e[order], tok[order], flat_g[order]
        starts = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
        rank = jnp.arange(gk, dtype=jnp.int32) - \
            starts[se].astype(jnp.int32)
        keep = rank < C
        slot = se.astype(jnp.int32) * C + jnp.where(keep, rank, 0)
        gathered = jnp.where(keep[:, None], xr[st], 0.0)
        buf = jnp.zeros((E * C, d), dtype=xr.dtype).at[slot].add(gathered)
        return buf, st, sg, keep, slot

    bufs, st, sg, keep, slot = jax.vmap(dispatch_one)(xg, expert, gate)
    bufs = sharder.act(bufs, ("moe_groups", None, "moe_token_d"))
    expert_in = sharder.act(bufs.reshape(G, E, C, d),
                            ("moe_groups", "experts", None, None))

    # EP einsums: "e" sharded over model on both operands -- no resharding.
    h = _act(cfg, jnp.einsum("gecd,edf->gecf", expert_in, p["we_gate"])) * \
        jnp.einsum("gecd,edf->gecf", expert_in, p["we_up"])
    h = sharder.act(h, ("moe_groups", "experts", None, None))
    out = jnp.einsum("gecf,efd->gecd", h.astype(x.dtype), p["we_down"])
    out = sharder.act(out, ("moe_groups", "experts", None, None))

    def combine_one(out_r, st, sg, keep, slot):
        contrib = jnp.where(keep[:, None],
                            out_r[slot] * sg[:, None].astype(out_r.dtype),
                            0.0)
        return jnp.zeros((g_size, d), out_r.dtype).at[st].add(contrib)

    out_rows = sharder.act(out.reshape(G, E * C, d),
                           ("moe_groups", None, "moe_token_d"))
    y = jax.vmap(combine_one)(out_rows, st, sg, keep, slot)
    y = sharder.act(y, ("moe_groups", None, "moe_token_d"))

    aux = _balance_loss(probs, expert[..., 0], E)
    return y.reshape(B, S, d), aux


def _balance_loss(probs: jax.Array, top1: jax.Array, E: int) -> jax.Array:
    """Switch-style load-balance auxiliary loss."""
    axes = tuple(range(top1.ndim))
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, E, dtype=f32), axis=axes)
    mean_prob = jnp.mean(probs, axis=axes)
    return (E * jnp.sum(frac_tokens * mean_prob)).astype(f32)


def moe_held(cfg: ModelConfig, p: dict, x: jax.Array, first: int = 0
             ) -> tuple[jax.Array, jax.Array]:
    """The part of a top-k MoE that experts ``first`` .. ``first + held``
    give, dropless: one chip's share under expert parallelism.

    The router keeps its full width: f32 logits over all ``n_experts``,
    the top ``k`` of them, gates a softmax over those ``k``.  The (token,
    expert) pairs that fall on held experts are sorted by expert into a
    buffer of ``T * min(k, held)`` rows, which no routing can overflow
    (a token picks an expert once), and three ragged expert matmuls
    (``ops.moe_experts``) compute only the tiles those rows cover.  The
    rows are added back to their tokens weighted by their gates, in the
    activations' dtype as the published Granite code does.  What the
    other experts would add is not computed: their chips add it under
    expert parallelism.  Returns (y, aux_loss).
    """
    B, S, d = x.shape
    E, k, held = cfg.n_experts, cfg.top_k, cfg.experts_held
    if not 0 <= first <= E - held:
        raise ValueError(f"experts {first}..{first + held - 1} are not "
                         f"among the router's {E}")
    T = B * S
    xt = x.reshape(T, d)
    logits = jnp.dot(xt.astype(f32), p["router"].astype(f32))   # (T, E)
    top, expert = jax.lax.top_k(logits, k)                      # (T, k)
    gates = jax.nn.softmax(top, axis=-1)

    # Sort the pairs by held expert; the pairs of other experts sort last.
    local = (expert - first).reshape(T * k)
    key = jnp.where((local >= 0) & (local < held), local, held)
    rows = -(-T * min(k, held) // 128) * 128        # tile-aligned bound
    pad = max(rows - T * k, 0)
    key = jnp.concatenate([key, jnp.full((pad,), held, key.dtype)])
    order = jnp.argsort(key, stable=True)[:rows]
    sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    valid = jnp.arange(rows) < jnp.sum(sizes)
    token = jnp.where(valid, order // k, 0)
    gate = jnp.concatenate([gates.reshape(T * k),
                            jnp.zeros((pad,), f32)])[order]

    xs = xt[token]                                              # (rows, d)
    gmm = functools.partial(ops.moe_experts, group_sizes=sizes,
                            use_pallas=cfg.use_pallas)
    h = _act(cfg, gmm(xs, p["we_gate"])) * gmm(xs, p["we_up"])
    # The gate scales a row before the (linear) down projection; the rows
    # past the routed ones hold no result and are dropped, never read.
    out = gmm(h * gate[:, None].astype(h.dtype), p["we_down"])
    y = jnp.zeros((T, d), x.dtype).at[jnp.where(valid, token, T)].add(
        out, mode="drop")
    return (y.reshape(B, S, d),
            _balance_loss(jax.nn.softmax(logits, axis=-1), expert[:, 0], E))


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) mixer
# ---------------------------------------------------------------------------

def spec_mamba(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    di, n, Hm = cfg.mamba_d_inner, cfg.ssm_state, cfg.mamba_heads
    proj_out = 2 * di + 2 * n + Hm
    return {
        "norm": ParamSpec((d,), f32, (None,), "zeros"),
        "in_proj": ParamSpec((d, proj_out), cfg.dtype,
                             ("embed", "mamba_inner"), "scaled"),
        "conv_w": ParamSpec((cfg.conv_kernel, di + 2 * n), cfg.dtype,
                            ("conv_k", "mamba_inner"), "scaled"),
        "conv_b": ParamSpec((di + 2 * n,), f32, ("mamba_inner",), "zeros"),
        "A_log": ParamSpec((Hm,), f32, (None,), "zeros"),
        "D": ParamSpec((Hm,), f32, (None,), "ones"),
        "dt_bias": ParamSpec((Hm,), f32, (None,), "zeros"),
        "ssm_norm": ParamSpec((di,), f32, (None,), "zeros"),
        "out_proj": ParamSpec((di, d), cfg.dtype, ("mamba_inner", "embed"),
                              "scaled"),
    }


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over seq: xbc (B, S, Cc), w (K, Cc)."""
    K = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(xbc, shape=xbc.shape).astype(f32)
    S = xbc.shape[1]
    for i in range(K):
        out = out + pad[:, i:i + S].astype(f32) * w[i].astype(f32)
    return (out + b.astype(f32)).astype(xbc.dtype)


def ssd_parallel(x: jax.Array, dt: jax.Array, B: jax.Array, C: jax.Array,
                 A: jax.Array, chunk: int = 256) -> jax.Array:
    """Chunk-parallel SSD: intra-chunk quadratic form + log-depth associative
    scan over chunk states.  Matches kernels.ref.ssd_scan_ref exactly.

    x (bh, s, dh); dt (bh, s); B, C (bh, s, n); A (bh,) -> y (bh, s, dh).
    No sequential while-loops: every FLOP is visible to XLA's cost model.
    """
    bh, s, dh = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    xf = x.astype(f32).reshape(bh, nc, chunk, dh)
    dtf = dt.astype(f32).reshape(bh, nc, chunk)
    Bf = B.astype(f32).reshape(bh, nc, chunk, n)
    Cf = C.astype(f32).reshape(bh, nc, chunk, n)
    a = A.astype(f32)[:, None, None]                        # (bh,1,1)

    adt = a * dtf                                           # (bh,nc,L)
    cum = jnp.cumsum(adt, axis=-1)                          # inclusive
    total = cum[..., -1]                                    # (bh,nc)

    # intra-chunk: scores[i,j] = exp(cum_i - cum_j) * dt_j  (i >= j).
    # Mask the EXPONENT, not the product: for i < j the difference is
    # positive and exp overflows to inf, which would poison gradients via
    # 0 * inf = NaN cotangents.
    li = jnp.arange(chunk)[:, None]
    lj = jnp.arange(chunk)[None, :]
    expnt = cum[..., :, None] - cum[..., None, :]            # (bh,nc,L,L)
    expnt = jnp.where(li >= lj, expnt, -1e30)
    gate = jnp.exp(expnt) * jnp.where(li >= lj, dtf[..., None, :], 0.0)
    scores = jnp.einsum("bcin,bcjn->bcij", Cf, Bf) * gate
    y_intra = jnp.einsum("bcij,bcjd->bcid", scores, xf)

    # per-chunk state contribution: sum_j exp(total - cum_j) dt_j B_j x_j^T
    w = jnp.exp(total[..., None] - cum) * dtf               # (bh,nc,L)
    s_c = jnp.einsum("bcjn,bcjd->bcnd", Bf * w[..., None], xf)  # (bh,nc,n,dh)

    # inter-chunk recurrence via associative scan (log depth, no while loop):
    # (d2, s2) o (d1, s1) = (d1*d2, s2 + d2*s1)  [state after = decay*before]
    dchunk = jnp.exp(total)                                 # (bh,nc)

    def combine(l, r):
        dl, sl = l
        dr, sr = r
        return dl * dr, sr + dr[..., None, None] * sl

    d_inc, s_inc = jax.lax.associative_scan(
        combine, (dchunk, s_c), axis=1)
    # exclusive prefix: state entering chunk c
    state_in = jnp.concatenate(
        [jnp.zeros_like(s_inc[:, :1]), s_inc[:, :-1]], axis=1)

    y_inter = jnp.einsum("bcin,bcnd->bcid", Cf * jnp.exp(cum)[..., None],
                         state_in)
    return (y_intra + y_inter).reshape(bh, s, dh).astype(x.dtype)


def mamba(cfg: ModelConfig, p: dict, x: jax.Array, sharder) -> jax.Array:
    B, S, d = x.shape
    di, n, Hm = cfg.mamba_d_inner, cfg.ssm_state, cfg.mamba_heads
    dh = cfg.mamba_head_dim

    proj = x @ p["in_proj"]                                  # (B,S,2di+2n+Hm)
    z, xin, Bc, Cc, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    xbc = _causal_conv(jnp.concatenate([xin, Bc, Cc], axis=-1),
                       p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc.astype(f32)).astype(x.dtype)
    xin, Bc, Cc = jnp.split(xbc, [di, di + n], axis=-1)

    dtv = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])     # (B,S,Hm)
    A = -jnp.exp(p["A_log"])                                 # (Hm,)

    xh = xin.reshape(B, S, Hm, dh).transpose(0, 2, 1, 3)     # (B,Hm,S,dh)
    xh = xh.reshape(B * Hm, S, dh)
    dth = dtv.transpose(0, 2, 1).reshape(B * Hm, S)
    Bh = jnp.broadcast_to(Bc[:, None], (B, Hm, S, n)).reshape(B * Hm, S, n)
    Ch = jnp.broadcast_to(Cc[:, None], (B, Hm, S, n)).reshape(B * Hm, S, n)
    Ah = jnp.broadcast_to(A[None, :], (B, Hm)).reshape(B * Hm)
    # Pin the flattened batch*heads sharding: the broadcasted B/C tensors
    # otherwise arrive replicated and the (nc, L, L) score intermediates
    # inside the SSD blow up memory by the model-axis factor.
    xh = sharder.act(xh, ("mamba_bh", None, None))
    dth = sharder.act(dth, ("mamba_bh", None))
    Bh = sharder.act(Bh, ("mamba_bh", None, None))
    Ch = sharder.act(Ch, ("mamba_bh", None, None))

    if cfg.use_pallas:
        y = ops.ssd_scan(xh, dth, Bh, Ch, Ah, use_pallas=True)
    else:
        y = ssd_parallel(xh, dth, Bh, Ch, Ah)
    y = y.reshape(B, Hm, S, dh).transpose(0, 2, 1, 3).reshape(B, S, di)
    y = y + (p["D"][None, None, :, None]
             * xin.reshape(B, S, Hm, dh).astype(f32)).reshape(B, S, di
                                                              ).astype(y.dtype)
    y = y * jax.nn.silu(z.astype(f32)).astype(y.dtype)
    y = rmsnorm(y, p["ssm_norm"], cfg.rms_eps)
    return y @ p["out_proj"]


def mamba_decode(cfg: ModelConfig, p: dict, x1: jax.Array,
                 conv_state: jax.Array, ssm_state: jax.Array):
    """Single-token Mamba-2 step.

    conv_state: (B, K-1, di+2n) trailing inputs; ssm_state: (B, Hm, n, dh).
    Returns (y, conv_state, ssm_state).
    """
    B = x1.shape[0]
    di, n, Hm = cfg.mamba_d_inner, cfg.ssm_state, cfg.mamba_heads
    dh = cfg.mamba_head_dim
    K = cfg.conv_kernel

    proj = x1[:, 0] @ p["in_proj"]                           # (B, ...)
    z, xin, Bc, Cc, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    xbc_new = jnp.concatenate([xin, Bc, Cc], axis=-1)        # (B, di+2n)

    full = jnp.concatenate([conv_state, xbc_new[:, None]], axis=1)  # (B,K,·)
    conv = jnp.einsum("bkc,kc->bc", full.astype(f32),
                      p["conv_w"].astype(f32)) + p["conv_b"]
    conv = jax.nn.silu(conv)
    xin, Bc, Cc = jnp.split(conv, [di, di + n], axis=-1)     # f32

    dtv = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])     # (B,Hm)
    A = -jnp.exp(p["A_log"])                                 # (Hm,)
    decay = jnp.exp(A[None] * dtv)                           # (B,Hm)
    xh = xin.reshape(B, Hm, dh)
    new_state = decay[..., None, None] * ssm_state + \
        (dtv[..., None, None] * Bc[:, None, :, None] * xh[:, :, None, :])
    y = jnp.einsum("bn,bhnd->bhd", Cc, new_state)            # (B,Hm,dh)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(B, 1, di)
    y = y * jax.nn.silu(z.astype(f32))[:, None]
    y = rmsnorm(y.astype(x1.dtype), p["ssm_norm"], cfg.rms_eps)
    return (y @ p["out_proj"],
            full[:, 1:].astype(conv_state.dtype),
            new_state.astype(ssm_state.dtype))
