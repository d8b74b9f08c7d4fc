"""Tuned kernel dispatch -- the runtime integration point of KLARAPTOR.

Each op consults the driver-program registry *immediately before launch*
(paper Section V-C: one IO call per kernel call, data parameters in, launch
parameters out), then invokes the Pallas kernel with the chosen BlockSpec
tiles.  With no driver registered the op falls back to the static heuristic
defaults; with ``use_pallas=False`` it runs the pure-jnp reference.  The
kernels run in the Pallas interpreter only on the CPU backend (the test
path); on a TPU every op compiles its kernel (``kernels/common.py``).

Because JAX shapes are static at trace time, the "launch" moment is trace
time: one decision per distinct shape, memoized in the driver's history
table, re-used by every execution of the compiled program -- the natural TPU
analogue of the paper's per-invocation decision with its runtime history.

``choose_or_default`` reads through the persistent driver-artifact cache
(core/cache.py): a driver tuned by any earlier process is loaded from disk on
first use, so these ops warm-start with tuned launch parameters even in a
process that never ran the tuner.  When a compiled launch plan covers the
shape (core/plan.py -- precompiled over the serving traffic envelope, lazily
filled for stragglers) dispatch is an O(1) probe of the plan table;
otherwise the loaded driver makes the decision in one vectorized
rational-program evaluation over the whole candidate table.

A *step plan* (core/step_plan.py) short-circuits all of that: when a
serving engine has pre-resolved every kernel config for its step shape,
ops read the frozen plan (explicit ``plan=`` argument, or the ambient
``use_step_plan`` context) and never touch the registry.  Step plans are
generation-checked, so the moment a refit or a pinned override lands they
go stale and dispatch falls back to ``choose_or_default``, where the new
state wins.

All of the above resolves at *trace* time -- one decision per distinct
shape, but also one recompile per distinct shape.  The ``in_graph=``
paths remove that last cost (ROADMAP item 2): pass a
``core.device_plan.BucketedDispatch`` plus the raw dims as traced values,
give the op envelope-padded operands (``core.buckets.pad_to`` to the
lattice's ``envelope_shape``), and the bucket's config is fetched
*inside* the compiled graph -- in-graph log2 rounding, a
``DevicePlanTable`` gather, and a ``jax.lax.switch`` over the table's
static config set (miss -> default-config branch).  One trace then
serves every raw shape; outputs live in the leading corner of the
envelope (zero padding is exact for matmul/colsum, causally masked for
flash attention, and layernorm's padded rows are sliced away), and the
caller slices ``[:m, :n]`` on the host where the raw dims are concrete.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.driver import choose_or_default, fit_tile as _fit_tile
from repro.core.step_plan import active_step_plan
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


def _switch_dispatch(disp, dims, branch_for_config, operands):
    """Run one Pallas branch per distinct config via ``jax.lax.switch``.

    ``disp.branch_index`` does the whole in-graph decision -- log2 bucket
    rounding of the traced raw dims, the ``DevicePlanTable`` gather, and
    the match against the table's static distinct-config set -- and the
    switch picks the matching branch (the last branch holds the default
    config for out-of-range / unplanned shapes).  Every branch sees the
    same static envelope-padded operand shapes, so the op compiles once
    no matter which raw shape arrives at run time.
    """
    idx, _ = disp.branch_index(dims)
    branches = [branch_for_config(cfg) for cfg in disp.config_dicts()]
    return jax.lax.switch(idx, branches, operands)


def _resolve(kernel: str, D: dict, default: dict, plan,
             span_attrs=None) -> dict:
    """Launch-config resolution for one op call.

    An explicit ``plan=`` argument wins; otherwise the ambient step plan
    (``core.step_plan.use_step_plan``) is consulted.  A plan hit is the
    zero-registry-traffic path; a miss -- including a plan gone stale
    because the registry generation moved (refit, new override) -- falls
    through to the full ``choose_or_default`` chain, which is what keeps
    pinned overrides ranked above any frozen step plan.  ``span_attrs``,
    if given, maps the chosen config to attributes of that chain's
    ``dispatch.choose`` span, to record what the launch will do.
    """
    if plan is None:
        plan = active_step_plan()
    if plan is not None:
        cfg = plan.resolve(kernel, D)
        if cfg is not None:
            return cfg
    # Only the fall-through is traced: dispatch happens at trace time (once
    # per distinct shape), and the plan-hit path above must stay span-free.
    with trace_span("dispatch.choose", kernel=kernel) as span:
        cfg = choose_or_default(kernel, D, default)
        if span_attrs is not None:
            span.set(**span_attrs(cfg))
        return cfg


@functools.lru_cache(maxsize=128)
def _batched_matmul(bm: int, bn: int, bk: int, interpret: bool,
                    out_dtype_name: str | None):
    """Cached vmapped batched-matmul closure, keyed on (tiles, out dtype).

    A per-call ``jax.vmap(lambda ...)`` is a fresh function identity every
    time, so an enclosing ``jax.jit`` re-traces on every batched matmul
    call; caching the closure (and threading ``y`` as an argument instead
    of capturing it) makes repeated batched calls hit the trace cache.
    """
    out_dtype = jnp.dtype(out_dtype_name) if out_dtype_name else None

    def one(a, y):
        return matmul_pallas(a, y, bm=bm, bn=bn, bk=bk, interpret=interpret,
                             out_dtype=out_dtype)

    return jax.vmap(one, in_axes=(0, None))


def matmul(x: jax.Array, y: jax.Array, *, use_pallas: bool = False,
           out_dtype=None, plan=None, in_graph=None,
           dims=None) -> jax.Array:
    """Tuned matmul over the last two dims; leading dims are batched.

    With ``in_graph=`` (a ``BucketedDispatch``) the operands must be 2-D
    and padded to the lattice envelope; ``dims`` carries the traced raw
    ``{m, n, k}`` and the config is resolved inside the graph.  The
    result is envelope-shaped -- the caller slices ``[:m, :n]`` (exact:
    padded k contributes zero partial products, padded rows/cols land in
    the sliced-off tail).
    """
    interpret = interpret_mode()
    if in_graph is not None:
        if x.ndim != 2:
            raise ValueError("in-graph matmul takes 2-D envelope-padded "
                             f"operands, got x.ndim={x.ndim}")
        M, K = x.shape
        N = y.shape[-1]
        if dims is None:
            dims = {"m": M, "n": N, "k": K}

        def branch(cfg):
            bm = _fit_tile(M, cfg["bm"], 8)
            bn = _fit_tile(N, cfg["bn"], 128)
            bk = _fit_tile(K, cfg["bk"], 128)

            def run(ops_):
                a, b = ops_
                return matmul_pallas(a, b, bm=bm, bn=bn, bk=bk,
                                     interpret=interpret,
                                     out_dtype=out_dtype)
            return run

        return _switch_dispatch(in_graph, dims, branch, (x, y))
    if not use_pallas:
        return ref.matmul_ref(x, y, out_dtype)
    m, k = x.shape[-2], x.shape[-1]
    n = y.shape[-1]
    key = "matmul_b16" if x.dtype == jnp.bfloat16 else "matmul_b32"
    cfg = _resolve(key, {"m": m, "n": n, "k": k}, MATMUL_DEFAULT, plan)
    bm = _fit_tile(m, cfg["bm"], 8)
    bn = _fit_tile(n, cfg["bn"], 128)
    bk = _fit_tile(k, cfg["bk"], 128)
    if x.ndim == 2:
        return matmul_pallas(x, y, bm=bm, bn=bn, bk=bk, interpret=interpret,
                             out_dtype=out_dtype)
    lead = x.shape[:-2]
    xf = x.reshape((-1,) + x.shape[-2:])
    batched = _batched_matmul(
        bm, bn, bk, interpret,
        jnp.dtype(out_dtype).name if out_dtype is not None else None)
    out = batched(xf, y)
    return out.reshape(lead + out.shape[-2:])


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    num_q_heads: int, num_kv_heads: int,
    causal: bool = True, window: int | None = None,
    softcap: float | None = None, scale: float | None = None,
    use_pallas: bool = False, q_chunk: int | None = None, plan=None,
    in_graph=None, dims=None,
) -> jax.Array:
    """(b*hq, sq, d) x (b*hkv, skv, d)^2 -> (b*hq, sq, d), tuned tiles.

    With ``in_graph=`` the operands are envelope-padded and ``dims``
    carries the traced raw ``{bh, sq, skv}``.  Only causal self-attention
    with aligned q/kv padding is safe here: a query row at position i
    attends to kv positions <= i, so zero rows in the padded kv tail are
    masked out for every *valid* query row, and padded query rows land in
    the sliced-off tail.
    """
    interpret = interpret_mode()
    if in_graph is not None:
        if not causal:
            raise ValueError(
                "in-graph flash attention requires causal=True: non-causal "
                "attention would read the zero-padded kv tail")
        BH, SQ, d_env = q.shape
        SKV = k.shape[1]
        if dims is None:
            dims = {"bh": BH, "sq": SQ, "skv": SKV}

        def branch(cfg):
            bq = _fit_tile(SQ, cfg["bq"], 8)
            bkv = _fit_tile(SKV, cfg["bkv"], 128)

            def run(ops_):
                qq, kk, vv = ops_
                return flash_attention_pallas(
                    qq, kk, vv, num_q_heads=num_q_heads,
                    num_kv_heads=num_kv_heads, bq=bq, bkv=bkv,
                    causal=causal, window=window, softcap=softcap,
                    scale=scale, interpret=interpret)
            return run

        return _switch_dispatch(in_graph, dims, branch, (q, k, v))
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
                   FLASH_DEFAULT, plan, span_attrs=blocks)
    bq, bkv = tiles(cfg)
    return flash_attention_pallas(
        q, k, v, num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
        bq=bq, bkv=bkv, causal=causal, window=window, softcap=softcap,
        scale=scale, interpret=interpret)


def moe_gmm(x: jax.Array, w: jax.Array, *, use_pallas: bool = False,
            plan=None) -> jax.Array:
    """(e, g, k) @ (e, k, n) -> (e, g, n), tuned tiles."""
    interpret = interpret_mode()
    if not use_pallas:
        return ref.moe_gmm_ref(x, w)
    e, g, k = x.shape
    n = w.shape[-1]
    cfg = _resolve("moe_gmm_b16", {"e": e, "g": g, "k": k, "n": n},
                   GMM_DEFAULT, plan)
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
             A: jax.Array, *, use_pallas: bool = False,
             plan=None) -> jax.Array:
    """Mamba-2 SSD scan with tuned chunk length."""
    interpret = interpret_mode()
    if not use_pallas:
        return ref.ssd_scan_ref(x, dt, B, C, A)
    bh, s, dh = x.shape
    n = B.shape[-1]
    cfg = _resolve(
        f"ssd_scan_h{dh}_n{n}", {"bh": bh, "s": s, "chunkflops": 1},
        SSD_DEFAULT, plan)
    chunk = _fit_tile(s, cfg["chunk"], 128) if s >= 128 else s
    return ssd_scan_pallas(x, dt, B, C, A, chunk=chunk, interpret=interpret)


# ---------------------------------------------------------------------------
# Auto-specced ops: no hand-written KernelSpec anywhere.  On first dispatch
# the Pallas kernel is introspected (repro.introspect traces its IR and
# derives the spec, including the tile-alignment granularities the _fit_tile
# calls above hard-code by hand), then launch parameters flow through the
# same choose_or_default chain: override > plan table > driver > default.
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
              use_pallas: bool = False, plan=None, in_graph=None,
              dims=None) -> jax.Array:
    """Fused layernorm + residual with an introspection-tuned row tile.

    With ``in_graph=`` the inputs are row-padded to the envelope and
    ``dims`` carries the traced raw ``{r}``.  Padded rows normalize a
    zero row (finite: eps keeps the rsqrt bounded) and end up in the
    sliced-off tail, so the valid rows are unaffected.
    """
    interpret = interpret_mode()
    if in_graph is not None:
        from .layernorm import layernorm_pallas

        R, c = x.shape
        ak = _layernorm_auto(c, 2 if x.dtype == jnp.bfloat16 else 4)
        if dims is None:
            dims = {"r": R}

        def branch(cfg):
            fitted = ak.fit_config(cfg, {"r": R})

            def run(ops_):
                xx, rr, gg, bb = ops_
                return layernorm_pallas(xx, rr, gg, bb, br=fitted["br"],
                                        eps=eps, interpret=interpret)
            return run

        return _switch_dispatch(in_graph, dims, branch,
                                (x, res, gamma, beta))
    if not use_pallas:
        return ref.layernorm_ref(x, res, gamma, beta, eps=eps)
    from .layernorm import layernorm_pallas

    r, c = x.shape
    ak = _layernorm_auto(c, 2 if x.dtype == jnp.bfloat16 else 4)
    cfg = ak.fit_config(_resolve(ak.name, {"r": r}, ak.defaults, plan),
                        {"r": r})
    return layernorm_pallas(x, res, gamma, beta, br=cfg["br"], eps=eps,
                            interpret=interpret)


def blocked_colsum(x: jax.Array, *, use_pallas: bool = False,
                   plan=None, in_graph=None, dims=None) -> jax.Array:
    """Column sums of (r, c) with introspection-tuned (br, bc) tiles.

    With ``in_graph=`` the input is envelope-padded and ``dims`` carries
    the traced raw ``{r, c}``; padded rows add zero to every column sum
    and padded columns land in the sliced-off tail, so the result is
    exact.
    """
    interpret = interpret_mode()
    if in_graph is not None:
        from .reduce import colsum_pallas

        R, C = x.shape
        ak = _colsum_auto(2 if x.dtype == jnp.bfloat16 else 4)
        if dims is None:
            dims = {"r": R, "c": C}

        def branch(cfg):
            fitted = ak.fit_config(cfg, {"r": R, "c": C})

            def run(ops_):
                (xx,) = ops_
                return colsum_pallas(xx, br=fitted["br"], bc=fitted["bc"],
                                     interpret=interpret)[0]
            return run

        return _switch_dispatch(in_graph, dims, branch, (x,))
    if not use_pallas:
        return ref.colsum_ref(x)
    from .reduce import colsum_pallas

    r, c = x.shape
    ak = _colsum_auto(2 if x.dtype == jnp.bfloat16 else 4)
    cfg = ak.fit_config(
        _resolve(ak.name, {"r": r, "c": c}, ak.defaults, plan),
        {"r": r, "c": c})
    return colsum_pallas(x, br=cfg["br"], bc=cfg["bc"],
                         interpret=interpret)[0]
