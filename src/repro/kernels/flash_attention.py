"""Flash attention Pallas kernel (forward), KLARAPTOR-tunable.

Launch parameters P = (bq, bkv): query/key tile lengths.  Grid
(batch*q_heads, q_blocks, kv_blocks) with the kv loop fastest; online
softmax carries (m, l, acc) in VMEM scratch across the kv loop.

Supports the assigned-architecture attention variants:
  * causal masking,
  * GQA (kv head sharing) via the k/v BlockSpec index map,
  * sliding-window (local) attention -- gemma2's alternating local layers,
  * logit soft-capping -- gemma2 (cap * tanh(s / cap)).

The kv-position mask is computed from broadcasted iotas, so non-divisible
final blocks and fully-masked blocks are correct (just not skipped; the
tuner's cost model sees the causal 0.5 factor instead).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import compiler_params, interpret_mode

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, window: int | None,
    softcap: float | None, bq: int, bkv: int, kv_steps: int,
):
    iq, ikv = pl.program_id(1), pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bkv, d)
    v = v_ref[0].astype(jnp.float32)          # (bkv, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (bq, bkv)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = ikv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), dtype=jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, qpos >= kpos)
    if window is not None:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:, :1]                                # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)            # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # (bq, bkv)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)

    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ikv == kv_steps - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_q_heads", "num_kv_heads", "bq", "bkv", "causal",
                     "window", "softcap", "scale", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,          # (b * num_q_heads, sq, d)
    k: jax.Array,          # (b * num_kv_heads, skv, d)
    v: jax.Array,          # (b * num_kv_heads, skv, d)
    *,
    num_q_heads: int,
    num_kv_heads: int,
    bq: int = 512,
    bkv: int = 512,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    bhq, sq, d = q.shape
    bhkv, skv, dk = k.shape
    assert d == dk and v.shape == k.shape
    assert bhq % num_q_heads == 0 and bhkv % num_kv_heads == 0
    assert bhq // num_q_heads == bhkv // num_kv_heads, "batch mismatch"
    group = num_q_heads // num_kv_heads
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (
        f"seq ({sq},{skv}) not divisible by tiles ({bq},{bkv})")
    scale = scale if scale is not None else d ** -0.5
    kv_steps = skv // bkv

    hq, hkv = num_q_heads, num_kv_heads

    def kv_index(bh, iq, ikv):
        return ((bh // hq) * hkv + (bh % hq) // group, ikv, 0)

    return pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            softcap=softcap, bq=bq, bkv=bkv, kv_steps=kv_steps),
        grid=(bhq, sq // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ikv: (bh, iq, 0)),
            pl.BlockSpec((1, bkv, d), kv_index),
            pl.BlockSpec((1, bkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ikv: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(interpret),
    )(q, k, v)
