"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

Implements the SSD algorithm of arXiv:2405.21060: the sequence is split into
chunks; within a chunk the output is a (masked, decay-weighted) attention-like
quadratic form, across chunks a low-rank state (n x dh) is carried by an
exponential-decay recurrence.  The chunk length is the KLARAPTOR launch
parameter for the attention-free mamba2-130m architecture (DESIGN.md section
4): it trades intra-chunk quadratic FLOPs against state-recurrence steps and
VMEM residency.

Grid (bh, n_chunks) with chunks sequential ("arbitrary"); the inter-chunk
state lives in a float32 VMEM scratch.

Scalar recurrence being reproduced exactly (the ref.py oracle):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * outer(B_t, x_t)     h: (n, dh)
    y_t = C_t @ h_t                                            y: (dh,)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

__all__ = ["ssd_scan_pallas"]


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, o_ref, state_ref,
                *, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)        # (L, dh)
    dt = dt_ref[0].astype(jnp.float32)      # (L, 128): dt_t on every lane
    B = b_ref[0].astype(jnp.float32)        # (L, n)
    C = c_ref[0].astype(jnp.float32)        # (L, n)
    a = a_ref[0]                            # (1, 128): decay rate A (< 0)

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    # Inclusive cumulative log-decay as a lower-triangular ones matmul (the
    # SSD form: Mosaic lowers no cumsum).  HIGHEST keeps the f32 sum exact
    # instead of rounding it through bf16 MXU passes.
    cum = jax.lax.dot_general(
        jnp.where(causal, 1.0, 0.0), a * dt, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                # (L, 128)
    cum0 = cum[:, :1]                                      # (L, 1)
    dt0 = dt[:, :1]                                        # (L, 1)

    # Intra-chunk quadratic term: scores[i, j] = exp(cum_i - cum_j) * dt_j
    # for i >= j (the decay from step j+1..i applied to input at j).  The
    # row forms come from lane-aligned (L, 128) transposes.
    cum_row = cum.T[:1, :]                                 # (1, L)
    dt_row = dt.T[:1, :]                                   # (1, L)
    # mask the exponent before exp: i < j entries would overflow to inf
    expnt = jnp.where(causal, cum0 - cum_row, -1e30)
    gate = jnp.exp(expnt) * jnp.where(causal, dt_row, 0.0)
    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * gate        # (L, L)
    y_intra = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (L, dh)

    # Inter-chunk term: y_i += C_i @ (exp(cum_i) * state_in).
    state_in = state_ref[...]                              # (n, dh)
    y_inter = jax.lax.dot_general(
        C * jnp.exp(cum0), state_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (L, dh)

    o_ref[0] = (y_intra + y_inter).astype(o_ref.dtype)

    # State update: state_out = exp(total) * state_in
    #             + sum_j exp(total - cum_j) * dt_j * outer(B_j, x_j).
    total = cum0[chunk - 1:, :]                            # (1, 1)
    w = jnp.exp(total - cum0) * dt0                        # (L, 1)
    # Broadcast the chunk decay along lanes first, then sublanes: Mosaic
    # will not broadcast a (1, 1) value in both at once.
    decay = jnp.exp(total * jnp.ones((1, state_in.shape[1]), jnp.float32))
    state_ref[...] = decay * state_in + jax.lax.dot_general(
        B * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (n, dh)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def ssd_scan_pallas(
    x: jax.Array,      # (bh, s, dh)
    dt: jax.Array,     # (bh, s)    step sizes (> 0)
    B: jax.Array,      # (bh, s, n)
    C: jax.Array,      # (bh, s, n)
    A: jax.Array,      # (bh,)      decay rates (< 0)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    bh, s, dh = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    # dt broadcast to a lane-aligned (bh, s, 128) plane; A as (bh, 1, 128).
    dt3 = jnp.broadcast_to(dt[:, :, None], (bh, s, 128))
    a3 = jnp.broadcast_to(A[:, None, None], (bh, 1, 128)).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(bh, s // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 128), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, 128), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, dh), jnp.float32)],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret_mode(interpret),
    )(x, dt3, B, C, a3)
