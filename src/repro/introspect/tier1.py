"""GridSpecs for the four tier-1 Pallas kernels.

These declare exactly what KLARAPTOR's users put in configuration files --
parameter names, candidate grids, probe hints, and the two genuinely
non-derivable FLOP policies (flash's causal 0.5 discount, ssd's
chunk-dependent density stated as an expression over the chunk) -- and
*nothing* structural.  ``spec_from_kernel`` over these must reproduce the
hand-written specs in ``core/kernel_spec.py`` behaviorally (same grid,
candidates, traffic, feasible set, chosen configs);
``tests/test_introspect.py`` and ``benchmarks/bench_introspect.py`` hold
that equivalence.

Production tier-1 dispatch keeps the hand specs; these GridSpecs exist as
the ground-truth check that introspection is faithful.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.kernel_spec import ssd_scan_flops

from .gridspec import GridSpec

__all__ = ["matmul_grid_spec", "flash_attention_grid_spec",
           "moe_gmm_grid_spec", "ssd_scan_grid_spec", "tier1_pairs"]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def matmul_grid_spec(dtype_bytes: int = 2) -> GridSpec:
    dt = jnp.bfloat16 if dtype_bytes == 2 else jnp.float32
    return GridSpec(
        name=f"matmul_b{dtype_bytes * 8}",
        data_params=("m", "n", "k"),
        program_params=("bm", "bn", "bk"),
        make_args=lambda D: (_sds((D["m"], D["k"]), dt),
                             _sds((D["k"], D["n"]), dt)),
        param_candidates={
            "bm": (8, 16, 32, 64, 128, 256, 512, 1024),
            "bn": (128, 256, 512, 1024, 2048),
            "bk": (128, 256, 512, 1024, 2048),
        },
        fit_vars={
            "mem_step": ("bm", "bn", "bk"),
            "cmp_step": ("bm", "bn", "bk"),
            "ovh_step": ("bm", "bn", "bk"),
        },
        defaults={"bm": 128, "bn": 512, "bk": 512},
        # flops_per_point and mxu_fraction are fully derived: the cost walk
        # sees one (bm, bk) x (bk, bn) MXU contraction per grid step.
    )


def flash_attention_grid_spec(head_dim: int = 128, causal: bool = True,
                              dtype_bytes: int = 2) -> GridSpec:
    dt = jnp.bfloat16 if dtype_bytes == 2 else jnp.float32
    return GridSpec(
        name=f"flash_attn_d{head_dim}" + ("_causal" if causal else ""),
        data_params=("bh", "sq", "skv"),
        program_params=("bq", "bkv"),
        # One query head per kv head keeps the GQA index map bh-affine while
        # tracing; the derived dependence (batch axis + kv axis) is the same
        # for any grouping.
        make_args=lambda D: (_sds((D["bh"], D["sq"], head_dim), dt),
                             _sds((D["bh"], D["skv"], head_dim), dt),
                             _sds((D["bh"], D["skv"], head_dim), dt)),
        call_kwargs={"num_q_heads": 1, "num_kv_heads": 1, "causal": causal},
        param_candidates={
            "bq": (128, 256, 512, 1024, 2048),
            "bkv": (128, 256, 512, 1024, 2048),
        },
        fit_vars={
            "mem_step": ("bq", "bkv"),
            "cmp_step": ("bq", "bkv"),
            "ovh_step": ("bq", "bkv"),
        },
        probe_hints={"bh": (2, 8)},
        # Causal masking halves the useful FLOPs; the dense jaxpr cannot
        # see that, so it is policy.  The MXU share (softmax VPU work) is a
        # measured estimate, exactly as in the hand spec.
        flop_scale=0.5 if causal else 1.0,
        mxu_fraction=0.85,
        defaults={"bq": 512, "bkv": 512},
    )


def moe_gmm_grid_spec(dtype_bytes: int = 2) -> GridSpec:
    dt = jnp.bfloat16 if dtype_bytes == 2 else jnp.float32
    return GridSpec(
        name=f"moe_gmm_b{dtype_bytes * 8}",
        data_params=("e", "g", "k", "n"),
        program_params=("bg", "bn", "bk"),
        make_args=lambda D: (_sds((D["e"], D["g"], D["k"]), dt),
                             _sds((D["e"], D["k"], D["n"]), dt)),
        param_candidates={
            "bg": (8, 16, 32, 64, 128, 256, 512),
            "bn": (128, 256, 512, 1024),
            "bk": (128, 256, 512, 1024),
        },
        probe_hints={"e": (2, 4)},
        defaults={"bg": 128, "bn": 512, "bk": 512},
    )


def ssd_scan_grid_spec(d_head: int = 64, d_state: int = 128,
                       dtype_bytes: int = 2) -> GridSpec:
    dt = jnp.bfloat16 if dtype_bytes == 2 else jnp.float32
    return GridSpec(
        name=f"ssd_scan_h{d_head}_n{d_state}",
        data_params=("bh", "s", "chunkflops"),
        program_params=("chunk",),
        make_args=lambda D: (_sds((D["bh"], D["s"], d_head), dt),
                             _sds((D["bh"], D["s"]), jnp.float32),
                             _sds((D["bh"], D["s"], d_state), dt),
                             _sds((D["bh"], D["s"], d_state), dt),
                             _sds((D["bh"],), jnp.float32)),
        param_candidates={"chunk": (128, 256, 512, 1024, 2048)},
        fit_vars={"mem_step": ("chunk",), "cmp_step": ("chunk",),
                  "ovh_step": ("chunk",)},
        probe_hints={"bh": (2, 8), "chunkflops": (1,)},
        # The intra-chunk work per point grows with the chunk length, so
        # the FLOP density depends on P -- the case the cost walk rejects.
        # Stated as the same expression the hand spec carries.
        flops_per_point=ssd_scan_flops(d_head, d_state),
        mxu_fraction=0.7,
        defaults={"chunk": 256},
    )


def tier1_pairs():
    """(pallas builder, GridSpec, hand spec) for the four tier-1 kernels."""
    from repro.core.kernel_spec import (flash_attention_spec, matmul_spec,
                                        moe_gmm_spec, ssd_scan_spec)
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.matmul import matmul_pallas
    from repro.kernels.moe_gmm import moe_gmm_pallas
    from repro.kernels.ssd_scan import ssd_scan_pallas

    return [
        (matmul_pallas, matmul_grid_spec(), matmul_spec()),
        (flash_attention_pallas, flash_attention_grid_spec(),
         flash_attention_spec()),
        (moe_gmm_pallas, moe_gmm_grid_spec(), moe_gmm_spec()),
        (ssd_scan_pallas, ssd_scan_grid_spec(), ssd_scan_spec()),
    ]
