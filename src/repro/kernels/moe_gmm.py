"""Grouped (expert) matmul Pallas kernel for MoE layers, KLARAPTOR-tunable.

Computes out[e, g, n] = x[e, g, k] @ w[e, k, n] -- the capacity-padded
expert-parallel matmul that dominates qwen3-moe / grok-1 / jamba MoE FLOPs.
Tokens are dispatched to expert slots (capacity g per expert) upstream
(models/moe.py); this kernel is the dense per-expert compute.

Launch parameters P = (bg, bn, bk).  Grid (e, i, j, l), k-loop fastest;
expert weight tiles are revisited across the token-block loop, which the
analytic traffic model in core/kernel_spec.moe_gmm_spec accounts for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

__all__ = ["moe_gmm_pallas"]


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bg", "bn", "bk", "interpret")
)
def moe_gmm_pallas(
    x: jax.Array,      # (e, g, k)
    w: jax.Array,      # (e, k, n)
    *,
    bg: int = 128,
    bn: int = 512,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    e, g, k = x.shape
    e2, k2, n = w.shape
    assert e == e2 and k == k2, (x.shape, w.shape)
    bg, bn, bk = min(bg, g), min(bn, n), min(bk, k)
    assert g % bg == 0 and n % bn == 0 and k % bk == 0, (
        f"group shape ({g},{n},{k}) not divisible by ({bg},{bn},{bk})")
    k_steps = k // bk
    return pl.pallas_call(
        functools.partial(_gmm_kernel, k_steps=k_steps),
        grid=(e, g // bg, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((1, bg, bk), lambda ex, i, j, l: (ex, i, l)),
            pl.BlockSpec((1, bk, bn), lambda ex, i, j, l: (ex, l, j)),
        ],
        out_specs=pl.BlockSpec((1, bg, bn), lambda ex, i, j, l: (ex, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, g, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bg, bn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret_mode(interpret),
    )(x, w)
