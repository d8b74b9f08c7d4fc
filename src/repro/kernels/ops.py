"""Tuned kernel dispatch -- the runtime integration point of KLARAPTOR.

Each op asks ``choose_or_default`` for its launch config *immediately
before launch* (paper Section V-C: one IO call per kernel call, data
parameters in, launch parameters out), then invokes the Pallas kernel with
the chosen BlockSpec tiles.  There is one path from a shape to a config:
a pinned per-shape override, else the registered driver (read through the
persistent artifact cache, core/cache.py, so a driver tuned by any earlier
process warm-starts here), else the op's static default.  With
``use_pallas=False`` the op runs the pure-jnp reference instead.  The
kernels run in the Pallas interpreter only on the CPU backend (the test
path); on a TPU every op compiles its kernel (``kernels/common.py``).

Because JAX shapes are static at trace time, the "launch" moment is trace
time: one decision per distinct shape and trace, kept in the driver's
decision history, re-used by every execution of the compiled program --
the natural TPU analogue of the paper's per-invocation decision with its
runtime history.  Each decision is a ``dispatch.choose`` span naming the
kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.driver import choose_or_default, fit_tile as _fit_tile
from repro.trace import trace_span

from . import ref
from .common import interpret_mode
from .flash_attention import flash_attention_pallas, flash_block_counts
from .matmul import matmul_pallas
from .moe_experts import moe_experts_pallas
from .moe_gmm import moe_gmm_pallas
from .ssd_scan import ssd_scan_pallas

__all__ = ["matmul", "flash_attention", "moe_gmm", "moe_experts",
           "ssd_scan", "layernorm", "blocked_colsum"]

# Static heuristic defaults (the "multiple of 32"-style baseline the paper
# contrasts with -- what a programmer would hard-code).
MATMUL_DEFAULT = {"bm": 128, "bn": 512, "bk": 512}
FLASH_DEFAULT = {"bq": 512, "bkv": 512}
GMM_DEFAULT = {"bg": 128, "bn": 512, "bk": 512}
SSD_DEFAULT = {"chunk": 256}


def _resolve(kernel: str, D: dict, default: dict,
             span_attrs=None) -> dict:
    """Launch config for one op call: ``choose_or_default`` inside a
    ``dispatch.choose`` span.  ``span_attrs``, if given, maps the chosen
    config to attributes of the span, to record what the launch will do.
    """
    with trace_span("dispatch.choose", kernel=kernel) as span:
        cfg = choose_or_default(kernel, D, default)
        if span_attrs is not None:
            span.set(**span_attrs(cfg))
        return cfg


@functools.lru_cache(maxsize=128)
def _matmul_fn(bm: int, bn: int, bk: int, interpret: bool,
               out_dtype_name: str | None, batched: bool):
    """Cached matmul closure, keyed on (tiles, out dtype, batched).

    A per-call ``jax.vmap(lambda ...)`` is a fresh function identity every
    time, so an enclosing ``jax.jit`` re-traces on every batched matmul
    call; caching the closure (and threading ``y`` as an argument instead
    of capturing it) makes repeated batched calls hit the trace cache.
    """
    out_dtype = jnp.dtype(out_dtype_name) if out_dtype_name else None

    def one(a, y):
        return matmul_pallas(a, y, bm=bm, bn=bn, bk=bk, interpret=interpret,
                             out_dtype=out_dtype)

    return jax.vmap(one, in_axes=(0, None)) if batched else one


def matmul(x: jax.Array, y: jax.Array, *, use_pallas: bool = False,
           out_dtype=None) -> jax.Array:
    """Tuned matmul over the last two dims; leading dims are batched."""
    if not use_pallas:
        return ref.matmul_ref(x, y, out_dtype)
    m, k = x.shape[-2], x.shape[-1]
    n = y.shape[-1]
    key = "matmul_b16" if x.dtype == jnp.bfloat16 else "matmul_b32"
    cfg = _resolve(key, {"m": m, "n": n, "k": k}, MATMUL_DEFAULT)
    fn = _matmul_fn(
        _fit_tile(m, cfg["bm"], 8), _fit_tile(n, cfg["bn"], 128),
        _fit_tile(k, cfg["bk"], 128), interpret_mode(),
        jnp.dtype(out_dtype).name if out_dtype is not None else None,
        x.ndim > 2)
    if x.ndim == 2:
        return fn(x, y)
    lead = x.shape[:-2]
    out = fn(x.reshape((-1,) + x.shape[-2:]), y)
    return out.reshape(lead + out.shape[-2:])


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    num_q_heads: int, num_kv_heads: int,
    causal: bool = True, window: int | None = None,
    softcap: float | None = None, scale: float | None = None,
    use_pallas: bool = False, q_chunk: int | None = None,
) -> jax.Array:
    """(b*hq, sq, d) x (b*hkv, skv, d)^2 -> (b*hq, sq, d), tuned tiles."""
    interpret = interpret_mode()
    if not use_pallas:
        return ref.flash_attention_ref(
            q, k, v, num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
            causal=causal, window=window, softcap=softcap, scale=scale,
            q_chunk=q_chunk)
    bh, sq, d = q.shape
    skv = k.shape[1]
    key = f"flash_attn_d{d}" + ("_causal" if causal else "")

    def tiles(cfg):
        return _fit_tile(sq, cfg["bq"], 8), _fit_tile(skv, cfg["bkv"], 128)

    def blocks(cfg):
        # How much of the score square the launch computes.
        return flash_block_counts(bh, sq, skv, *tiles(cfg), causal, window)

    cfg = _resolve(key, {"bh": bh, "sq": sq, "skv": skv},
                   FLASH_DEFAULT, span_attrs=blocks)
    bq, bkv = tiles(cfg)
    return flash_attention_pallas(
        q, k, v, num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
        bq=bq, bkv=bkv, causal=causal, window=window, softcap=softcap,
        scale=scale, interpret=interpret)


def moe_gmm(x: jax.Array, w: jax.Array, *,
            use_pallas: bool = False) -> jax.Array:
    """(e, g, k) @ (e, k, n) -> (e, g, n), tuned tiles."""
    interpret = interpret_mode()
    if not use_pallas:
        return ref.moe_gmm_ref(x, w)
    e, g, k = x.shape
    n = w.shape[-1]
    cfg = _resolve("moe_gmm_b16", {"e": e, "g": g, "k": k, "n": n},
                   GMM_DEFAULT)
    bg = _fit_tile(g, cfg["bg"], 8)
    bn = _fit_tile(n, cfg["bn"], 128)
    bk = _fit_tile(k, cfg["bk"], 128)
    return moe_gmm_pallas(x, w, bg=bg, bn=bn, bk=bk, interpret=interpret)


def moe_experts(lhs: jax.Array, rhs: jax.Array, *, group_sizes: jax.Array,
                use_pallas: bool = False) -> jax.Array:
    """Ragged expert matmul: rows of (m, k) ``lhs`` sorted by expert,
    ``group_sizes[e]`` of them for expert e of (experts, k, n) ``rhs``.
    Rows past the last expert's come back zero (XLA) or undefined
    (Pallas).  The Pallas kernel launches at fixed tiles: its grid's
    length is data, which the tuner's specs cannot state yet."""
    if not use_pallas:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return moe_experts_pallas(lhs, rhs, group_sizes,
                              tm=math.gcd(lhs.shape[0], 512),
                              interpret=interpret_mode())


def ssd_scan(x: jax.Array, dt: jax.Array, B: jax.Array, C: jax.Array,
             A: jax.Array, *, use_pallas: bool = False) -> jax.Array:
    """Mamba-2 SSD scan with tuned chunk length."""
    interpret = interpret_mode()
    if not use_pallas:
        return ref.ssd_scan_ref(x, dt, B, C, A)
    bh, s, dh = x.shape
    n = B.shape[-1]
    cfg = _resolve(
        f"ssd_scan_h{dh}_n{n}", {"bh": bh, "s": s, "chunkflops": 1},
        SSD_DEFAULT)
    chunk = _fit_tile(s, cfg["chunk"], 128) if s >= 128 else s
    return ssd_scan_pallas(x, dt, B, C, A, chunk=chunk, interpret=interpret)


# ---------------------------------------------------------------------------
# Auto-specced ops: no hand-written KernelSpec anywhere.  On first dispatch
# the Pallas kernel is introspected (repro.introspect traces its IR and
# derives the spec, including the tile-alignment granularities the _fit_tile
# calls above hard-code by hand), then launch parameters flow through the
# same choose_or_default chain: override > driver > default.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _layernorm_auto(c: int, dtype_bytes: int):
    from repro.introspect import auto_register

    from .layernorm import layernorm_grid_spec, layernorm_pallas
    return auto_register(layernorm_pallas,
                         layernorm_grid_spec(c, dtype_bytes))


@functools.lru_cache(maxsize=4)
def _colsum_auto(dtype_bytes: int):
    from repro.introspect import auto_register

    from .reduce import colsum_grid_spec, colsum_pallas
    return auto_register(colsum_pallas, colsum_grid_spec(dtype_bytes))


def layernorm(x: jax.Array, res: jax.Array, gamma: jax.Array,
              beta: jax.Array, *, eps: float = 1e-6,
              use_pallas: bool = False) -> jax.Array:
    """Fused layernorm + residual with an introspection-tuned row tile."""
    if not use_pallas:
        return ref.layernorm_ref(x, res, gamma, beta, eps=eps)
    from .layernorm import layernorm_pallas

    r, c = x.shape
    ak = _layernorm_auto(c, 2 if x.dtype == jnp.bfloat16 else 4)
    cfg = ak.fit_config(_resolve(ak.name, {"r": r}, ak.defaults), {"r": r})
    return layernorm_pallas(x, res, gamma, beta, br=cfg["br"], eps=eps,
                            interpret=interpret_mode())


def blocked_colsum(x: jax.Array, *, use_pallas: bool = False) -> jax.Array:
    """Column sums of (r, c) with introspection-tuned (br, bc) tiles."""
    if not use_pallas:
        return ref.colsum_ref(x)
    from .reduce import colsum_pallas

    r, c = x.shape
    ak = _colsum_auto(2 if x.dtype == jnp.bfloat16 else 4)
    cfg = ak.fit_config(_resolve(ak.name, {"r": r, "c": c}, ak.defaults),
                        {"r": r, "c": c})
    return colsum_pallas(x, br=cfg["br"], bc=cfg["bc"],
                         interpret=interpret_mode())[0]
