"""Fused layernorm + residual Pallas kernel -- auto-specced, zero hand spec.

y = (x - mean) / sqrt(var + eps) * gamma + beta + residual, normalized over
the feature axis.  The row tile ``br`` is the launch parameter: it trades
VMEM residency (three (br, c) planes plus the broadcast gamma/beta rows)
against grid dispatch overhead.  The feature width ``c`` is a literal of
the kernel instance (like flash attention's head_dim), so the derived spec
is per-width: ``layernorm_c{c}``.

No KernelSpec exists for this kernel anywhere: ``repro.introspect`` derives
it from this file's traced IR (see ``layernorm_grid_spec``), and the ops
wrapper dispatches through the derived spec -- the "tune any kernel without
annotations" property of the paper's LLVM pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.introspect import GridSpec

from .common import compiler_params, interpret_mode

__all__ = ["layernorm_pallas", "layernorm_grid_spec"]


def _ln_kernel(x_ref, r_ref, g_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                      # (br, c)
    mu = jnp.mean(x, axis=1, keepdims=True)                 # (br, 1)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = (y + r_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "eps", "interpret"))
def layernorm_pallas(
    x: jax.Array,          # (r, c)
    res: jax.Array,        # (r, c) residual stream
    gamma: jax.Array,      # (c,)
    beta: jax.Array,       # (c,)
    *,
    br: int = 128,
    eps: float = 1e-6,
    interpret: bool = False,
) -> jax.Array:
    r, c = x.shape
    assert res.shape == (r, c) and gamma.shape == (c,) and beta.shape == (c,)
    br = min(br, r)
    assert r % br == 0, f"rows {r} not divisible by tile {br}"
    # gamma/beta as (1, c) planes: fetched once, resident across every row
    # block (their index map ignores the grid axis -- the block-residency
    # case the introspection dependence analysis detects).
    g2 = gamma.reshape(1, c).astype(jnp.float32)
    b2 = beta.reshape(1, c).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret_mode(interpret),
    )(x, res, g2, b2)


def layernorm_grid_spec(c: int, dtype_bytes: int = 2,
                        eps: float = 1e-6) -> GridSpec:
    """Tunable-interface declaration for ``spec_from_kernel``.

    Only the interface and candidate policy -- grid, tiles, residency,
    FLOPs, VMEM footprint and constraints are all derived from the traced
    kernel.
    """
    dt = jnp.bfloat16 if dtype_bytes == 2 else jnp.float32
    return GridSpec(
        name=f"layernorm_c{c}_b{dtype_bytes * 8}",
        data_params=("r",),
        program_params=("br",),
        make_args=lambda D: (
            jax.ShapeDtypeStruct((D["r"], c), dt),
            jax.ShapeDtypeStruct((D["r"], c), dt),
            jax.ShapeDtypeStruct((c,), jnp.float32),
            jax.ShapeDtypeStruct((c,), jnp.float32),
        ),
        call_kwargs={"eps": eps},
        param_candidates={"br": (8, 16, 32, 64, 128, 256, 512, 1024, 2048)},
        fit_vars={"mem_step": ("br",), "cmp_step": ("br",),
                  "ovh_step": ("br",)},
        defaults={"br": 128},
    )
