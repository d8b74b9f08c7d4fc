"""Blocked column-sum reduction Pallas kernel -- auto-specced, zero hand spec.

out[c] = sum_r x[r, c], tiled (br, bc) with the row loop as the fastest
(sequential) grid axis: partial sums accumulate in a (8, bc) float32 VMEM
scratch, and the output block is written once per column block at the last
row step -- its index map ignores the row axis, which is exactly the block
residency the introspection dependence analysis derives (the output tile is
fetched once per *column* block, not once per grid step).

The launch parameters (br, bc) trade DMA transfer size against VMEM
residency and dispatch overhead; no hand-written KernelSpec exists --
``repro.introspect`` derives it from the traced IR (``colsum_grid_spec``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.introspect import GridSpec

from .common import compiler_params, interpret_mode

__all__ = ["colsum_pallas", "colsum_grid_spec"]


def _colsum_kernel(x_ref, o_ref, acc_ref, *, r_steps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = jnp.sum(x_ref[...].astype(jnp.float32), axis=0, keepdims=True)
    acc_ref[...] += jnp.broadcast_to(part, acc_ref.shape)   # (8, bc)

    @pl.when(pl.program_id(1) == r_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "bc", "interpret"))
def colsum_pallas(
    x: jax.Array,          # (r, c)
    *,
    br: int = 256,
    bc: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Column sums of x as an (8, c) float32 plane (rows identical; the
    sublane-aligned minimum output tile on TPU).  Callers take row 0."""
    r, c = x.shape
    br, bc = min(br, r), min(bc, c)
    assert r % br == 0 and c % bc == 0, (
        f"shape ({r},{c}) not divisible by tile ({br},{bc})")
    return pl.pallas_call(
        functools.partial(_colsum_kernel, r_steps=r // br),
        grid=(c // bc, r // br),
        in_specs=[pl.BlockSpec((br, bc), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((8, bc), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((8, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, bc), jnp.float32)],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret_mode(interpret),
    )(x)


def colsum_grid_spec(dtype_bytes: int = 2) -> GridSpec:
    """Tunable-interface declaration for ``spec_from_kernel``."""
    dt = jnp.bfloat16 if dtype_bytes == 2 else jnp.float32
    return GridSpec(
        name=f"colsum_b{dtype_bytes * 8}",
        data_params=("r", "c"),
        program_params=("br", "bc"),
        make_args=lambda D: (jax.ShapeDtypeStruct((D["r"], D["c"]), dt),),
        param_candidates={
            "br": (8, 16, 32, 64, 128, 256, 512, 1024, 2048),
            "bc": (128, 256, 512, 1024, 2048),
        },
        defaults={"br": 256, "bc": 512},
    )
