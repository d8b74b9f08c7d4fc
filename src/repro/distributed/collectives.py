"""Collective helpers: hierarchical + compressed data-parallel reductions.

``hierarchical_pmean``: reduce over the fast ICI axis ("data") first, then
the slow cross-pod axis ("pod") -- the standard two-level schedule that
keeps DCN traffic at 1/pod_size of a flat all-reduce.

``compressed_pmean``: int8-quantized cross-pod reduction with error
feedback handled by the caller (optim/compression.py): within-pod reduction
runs at full precision over ICI; only the pod-level exchange is quantized.

Both are written for use inside jax.shard_map with a ("pod", "data", ...)
mesh; on meshes without a "pod" axis they degrade to plain psums.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["hierarchical_pmean", "compressed_pmean"]

f32 = jnp.float32


def _has_axis(name: str) -> bool:
    try:
        jax.lax.axis_index(name)
        return True
    except NameError:
        return False


def hierarchical_pmean(tree, ici_axis: str = "data", dcn_axis: str = "pod"):
    """Mean over (ici_axis, dcn_axis) as two stages: ICI first, DCN second."""
    tree = jax.tree.map(lambda g: jax.lax.pmean(g, ici_axis), tree)
    return jax.tree.map(lambda g: jax.lax.pmean(g, dcn_axis), tree)


def compressed_pmean(tree, ici_axis: str = "data", dcn_axis: str = "pod"):
    """Full-precision ICI mean, int8 cross-pod mean (per-tensor scales).

    Quantization residual is returned so the caller can fold it into an
    error-feedback buffer: returns (mean_tree, residual_tree).
    """
    tree = jax.tree.map(lambda g: jax.lax.pmean(g, ici_axis), tree)

    def one(g):
        g32 = g.astype(f32)
        scale = jnp.max(jnp.abs(g32)) / 127.0 + 1e-30
        q = jnp.clip(jnp.round(g32 / scale), -127, 127)
        # psum int-valued f32 (int8 summation would overflow at 2 pods max
        # anyway; the wire format in a real DCN transport is the int8 q).
        qsum = jax.lax.psum(q * scale, dcn_axis)
        n = jax.lax.psum(jnp.ones((), f32), dcn_axis)
        mean = (qsum / n).astype(g.dtype)
        residual = g32 - (q * scale)
        return mean, residual

    flat, treedef = jax.tree.flatten(tree)
    means, residuals = zip(*(one(g) for g in flat)) if flat else ((), ())
    return (jax.tree.unflatten(treedef, list(means)),
            jax.tree.unflatten(treedef, list(residuals)))
