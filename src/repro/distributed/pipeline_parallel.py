"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The layer-group stack (models/transformer.py) is split into ``n_stages``
contiguous segments placed along a "pipe" mesh axis; microbatches stream
through with jax.lax.ppermute boundary transfers inside shard_map.  The
schedule below is the classic GPipe loop: with M microbatches and S stages,
step t in [0, M + S - 1) runs stage s on microbatch t - s; bubble fraction
is (S - 1) / (M + S - 1).

This module provides the *schedule machinery* generically over a per-stage
apply function: the hillclimb experiments drive it with transformer groups,
and the unit tests with small MLP stages (mesh of 4-8 CPU devices).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["gpipe_forward", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def gpipe_forward(
    stage_fn: Callable,       # (stage_params, x) -> y, same shape
    stage_params,             # pytree; leaves have leading dim n_stages
    x: jax.Array,             # (n_micro, micro_batch, ...) global input
    mesh: Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run x through n_stages pipeline stages living on mesh axis ``axis``.

    Returns the stacked outputs (n_micro, micro_batch, ...).  Inside the
    shard_map each device holds one stage's parameters; activations flow
    stage -> stage+1 via ppermute each tick.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1

    def body(params, xs):  # runs per-stage (shard_map)
        params = jax.tree.map(lambda p: p[0], params)   # local stage params
        stage = jax.lax.axis_index(axis)
        xs = xs[0]                                      # (n_micro, mb, ...)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros_like(xs)                        # collected outputs
        carry = jnp.zeros(mb_shape, xs.dtype)           # incoming activation

        def tick(t, state):
            carry, buf = state
            # stage 0 ingests microbatch t (if valid); others use carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                  keepdims=False)
            inp = jnp.where(stage == 0, inject, carry)
            # compute only while this stage has valid work: t in
            # [stage, stage + n_micro); harmless extra compute otherwise
            out = stage_fn(params, inp)
            # last stage banks its result for microbatch t - (n_stages - 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid_out = jnp.logical_and(
                stage == n_stages - 1,
                jnp.logical_and(t - (n_stages - 1) >= 0,
                                t - (n_stages - 1) < n_micro))
            buf = jnp.where(
                valid_out,
                jax.lax.dynamic_update_index_in_dim(
                    buf, out, out_idx, 0),
                buf)
            # shift activations stage s -> s+1
            carry = jax.lax.ppermute(
                out, axis, [(i, i + 1) for i in range(n_stages - 1)])
            return carry, buf

        _, buf = jax.lax.fori_loop(0, ticks, tick, (carry, buf))
        # only the last stage holds outputs; broadcast to all for out_specs
        total = jax.lax.psum(
            jnp.where(stage == n_stages - 1, buf, jnp.zeros_like(buf)), axis)
        return total[None]

    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    # stage_params: leading dim n_stages -> sharded over axis; x replicated
    # per stage via a broadcast leading axis.
    xs = jnp.broadcast_to(x[None], (n_stages,) + x.shape)
    out = sm(stage_params, xs)
    return out[0]
