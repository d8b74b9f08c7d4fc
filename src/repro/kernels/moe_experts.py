"""Ragged grouped matmul for the experts a chip holds, dropless.

Computes out[r] = lhs[r] @ rhs[g] for every row r of group g, where the
rows of lhs are sorted by group and ``group_sizes`` says how many each
group has: the expert matmul of an MoE layer whose (token, expert) pairs
were sorted by expert.  Rows past the last group are never computed and
their output is left undefined; the caller masks them.

The grid's middle axis runs over the row tiles the groups actually cover
(a length computed on the device from ``group_sizes``), so the work grows
with the rows routed here, not with the static size of the row buffer.
A tile that straddles two groups is visited once per group, and each
visit stores only its own group's rows.  The tile metadata is
``megablox.gmm``'s (``make_group_metadata``); the kernel carries a pinned
``name`` so the device trace attributes its time.  Launch tiles are
fixed: the tuner does not yet handle a grid whose length is data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from .common import compiler_params, interpret_mode

__all__ = ["moe_experts_pallas"]


def _kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, lhs_ref, rhs_ref,
            out_ref, acc_ref, *, tm: int, k_steps: int):
    grid_id, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == k_steps - 1)
    def _store():
        group = group_ids_ref[grid_id]
        row = (m_tile_ids_ref[grid_id] * tm
               + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0))
        mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn", "interpret"))
def moe_experts_pallas(lhs: jax.Array, rhs: jax.Array,
                       group_sizes: jax.Array, *, tm: int = 512,
                       tk: int = 2048, tn: int = 1024,
                       interpret: bool = False) -> jax.Array:
    """(m, k) rows sorted by group, (groups, k, n) weights, (groups,) int32
    sizes -> (m, n) in lhs's dtype, accumulated in f32."""
    m, k = lhs.shape
    groups, k2, n = rhs.shape
    assert k == k2 and group_sizes.shape == (groups,), (
        lhs.shape, rhs.shape, group_sizes.shape)
    tm, tk, tn = min(tm, m), min(tk, k), min(tn, n)
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (
        f"shape ({m},{k},{n}) not divisible by tiles ({tm},{tk},{tn})")
    (offsets, group_ids, m_tile_ids), tiles = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=groups,
        visit_empty_groups=False)
    k_steps = k // tk

    def lhs_index(j, t, l, offsets, group_ids, m_tile_ids):
        return m_tile_ids[t], l

    def rhs_index(j, t, l, offsets, group_ids, m_tile_ids):
        return group_ids[t], l, j

    def out_index(j, t, l, offsets, group_ids, m_tile_ids):
        return m_tile_ids[t], j

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, k_steps=k_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, tiles, k_steps),
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((1, tk, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=compiler_params("parallel", "arbitrary",
                                        "arbitrary"),
        interpret=interpret_mode(interpret),
        # The device op's name, whatever jitted function wraps the call.
        name="moe_experts_pallas",
    )(offsets, group_ids, m_tile_ids, lhs, rhs)
