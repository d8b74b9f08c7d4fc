"""Tiled matmul Pallas kernel -- the canonical KLARAPTOR-tunable kernel.

Launch parameters P = (bm, bn, bk): BlockSpec tile sizes.  Grid (i, j, l)
with the k-loop (l) fastest, matching core/kernel_spec.matmul_spec -- the
analytic workload description the tuner and the simulator share.

TPU mapping: bm/bn/bk are chosen so two pipeline stage buffers fit VMEM
(the occupancy constraint), bn/bk are lane-aligned (128) and bm is
sublane-aligned (8).  A float32 VMEM scratch accumulates partial products
across the k loop; the MXU sees (bm, bk) x (bk, bn) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

__all__ = ["matmul_pallas"]


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype")
)
def matmul_pallas(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = 128,
    bn: int = 512,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """C[m, n] = x[m, k] @ y[k, n] with explicit VMEM tiling.

    Requires m % bm == n % bn == k % bk == 0 (the launch-config enumerator
    only proposes divisible tiles for the sizes it is given; the ops-level
    wrapper pads otherwise).
    """
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{n},{k}) not divisible by tile ({bm},{bn},{bk})")
    out_dtype = out_dtype or x.dtype
    k_steps = k // bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(interpret),
    )(x, y)
