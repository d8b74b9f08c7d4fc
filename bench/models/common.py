"""Pieces the plain references share: the precision they compute in,
RMS norm, and the seeded weights.

A reference computes in float32 with every matrix product at full
precision (a TPU otherwise rounds f32 operands through bf16).  Its control
computes in fp8 (e4m3) instead, the step below the bf16 that the
configurations state, as fp8 inference commonly does: each matrix
product's operands are scaled per tensor into fp8's range and rounded to
it, while products accumulate in f32 and the residual stream stays wide.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to e4m3 under a per-tensor scale, back in f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(f32) * scale


def operand_rounding(quant: str | None):
    """The rounding applied to every matrix-product operand."""
    if quant is None:
        return lambda x: x
    if quant == "fp8":
        return fp8
    raise ValueError(f"unknown precision {quant!r}")


def einsum(spec: str, a: jax.Array, b: jax.Array, rnd) -> jax.Array:
    return jnp.einsum(spec, rnd(a.astype(f32)), rnd(b.astype(f32)),
                      precision=HIGHEST, preferred_element_type=f32)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMS norm with the weight stored as an offset from 1 (the layout of
    the program's parameters; a published weight w is 1 + this one)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(f32))


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def draw_weights(abstract, key: jax.Array, rule) -> dict:
    """Weights for the program's parameter tree ``abstract`` (shapes and
    dtypes only), each leaf drawn by ``rule(name, shape, key)`` in f32 and
    cast to the leaf's dtype.  Called under one ``jax.jit``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        value = rule(leaf_name(path), leaf.shape, jax.random.fold_in(key, i))
        out.append(value.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def normal(key, shape, std: float) -> jax.Array:
    return std * jax.random.normal(key, shape, f32)
