"""Compile every kernel for a described TPU v5e at real widths.

Interpret mode accepts what Mosaic refuses: VMEM over the scoped limit,
primitives with no TPU lowering, misaligned slices.  These tests compile
each op of ``kernels/ops.py`` for one chip of a described (not attached)
``v5e:2x2`` topology, with the config that a ``V5eSimulator``-built driver
chooses at that shape, and check that a Mosaic kernel (``tpu_custom_call``)
came out.  Widths: llama3.2-1b for the ops on its path, mamba2-130m for the
SSD scan, qwen3-moe-235b-a22b for the expert matmul, granite-4.0-h-small's
chip share for the ragged matmul of the experts held (fixed tiles), plus
the matmul and flash shapes where the tuner used to choose tiles the
compiler refused.  The tuned kernels and the ragged matmul are also
compiled without their jitted wrappers, to check that each keeps the
device op name the benchmark's readers match.
"""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import (Klaraptor, V5eSimulator, flash_attention_spec,
                        matmul_spec, moe_gmm_spec, registry,
                        set_choice_listener, ssd_scan_spec)
from repro.introspect import spec_from_kernel
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_experts import moe_experts_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.layernorm import layernorm_grid_spec, layernorm_pallas
from repro.kernels.reduce import colsum_grid_spec, colsum_pallas
from repro.models.layers import moe_capacity

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from bench.family_readers import KERNEL_OPS  # noqa: E402

LLAMA = get_config("llama3.2-1b")
MAMBA = get_config("mamba2-130m")
MOE = get_config("qwen3-moe-235b-a22b")
TOKENS = 2 * 1024                    # a (2, 1024) prefill batch
BF, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def drivers():
    specs = [
        matmul_spec(), flash_attention_spec(head_dim=128),
        flash_attention_spec(head_dim=LLAMA.head_dim), moe_gmm_spec(),
        ssd_scan_spec(d_head=MAMBA.mamba_head_dim, d_state=MAMBA.ssm_state),
        spec_from_kernel(layernorm_pallas, layernorm_grid_spec(LLAMA.d_model)),
        spec_from_kernel(colsum_pallas, colsum_grid_spec()),
    ]
    tuner = Klaraptor(V5eSimulator(), cache=False)
    return [tuner.build_driver(s, register=False).driver for s in specs]


@pytest.fixture()
def chosen(drivers):
    """Registers freshly built drivers and records the config each
    launch decision chose, so a test can name the config it compiled."""
    registry.clear()
    for drv in drivers:
        registry.register(drv)
    seen = {}
    set_choice_listener(lambda ev: seen.__setitem__(ev.kernel, ev.config))
    yield seen
    set_choice_listener(None)
    registry.clear()


def _attn(bh_q, bh_kv, s, d, heads_q, heads_kv):
    return ([(bh_q, s, d, BF), (bh_kv, s, d, BF), (bh_kv, s, d, BF)],
            functools.partial(ops.flash_attention, num_q_heads=heads_q,
                              num_kv_heads=heads_kv, causal=True))


_E, _G = MOE.n_experts, moe_capacity(MOE, MOE.moe_group)
_BH = 2 * MAMBA.mamba_heads
_N, _DH = MAMBA.ssm_state, MAMBA.mamba_head_dim

CASES = {
    # (m, k) @ (k, n): refused before the VMEM budget was shared.
    "matmul_512x8192x2048": ("matmul_b16", [(512, 2048, BF), (2048, 8192, BF)],
                             ops.matmul),
    "matmul_2048x8192x2048": ("matmul_b16",
                              [(2048, 2048, BF), (2048, 8192, BF)],
                              ops.matmul),
    "matmul_llama_down_proj": ("matmul_b16",
                               [(TOKENS, LLAMA.d_ff, BF),
                                (LLAMA.d_ff, LLAMA.d_model, BF)], ops.matmul),
    # bh=128, s=4096, d=128: refused before the (bq, bkv) f32 scores were
    # counted in the footprint.
    "flash_d128_s4096": ("flash_attn_d128_causal",
                         *_attn(128, 32, 4096, 128, 32, 8)),
    "flash_llama": ("flash_attn_d64_causal",
                    *_attn(2 * LLAMA.n_heads, 2 * LLAMA.n_kv_heads, 1024,
                           LLAMA.head_dim, LLAMA.n_heads, LLAMA.n_kv_heads)),
    "moe_gmm_qwen3_moe": ("moe_gmm_b16",
                          [(_E, _G, MOE.d_model, BF),
                           (_E, MOE.d_model, MOE.moe_d_ff, BF)], ops.moe_gmm),
    "ssd_scan_mamba2": ("ssd_scan_h64_n128",
                        [(_BH, 1024, _DH, BF), (_BH, 1024, F32),
                         (_BH, 1024, _N, BF), (_BH, 1024, _N, BF),
                         (_BH, F32)], ops.ssd_scan),
    "layernorm_llama": (f"layernorm_c{LLAMA.d_model}_b16",
                        [(TOKENS, LLAMA.d_model, BF),
                         (TOKENS, LLAMA.d_model, BF),
                         (LLAMA.d_model, F32), (LLAMA.d_model, F32)],
                        ops.layernorm),
    "colsum_llama": ("colsum_b16", [(TOKENS, LLAMA.d_model, BF)],
                     ops.blocked_colsum),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_to_mosaic(case, one_chip, chosen, monkeypatch):
    kernel, shapes, op = CASES[case]
    # The ops take their CPU (interpret) branch on this backend; compiling
    # for the described chip needs the TPU branch.
    monkeypatch.setattr(ops, "interpret_mode", lambda requested=True: False)
    args = [jax.ShapeDtypeStruct(s[:-1], s[-1], sharding=one_chip)
            for s in shapes]
    fn = functools.partial(op, use_pallas=True)
    compiled = jax.jit(fn).lower(*args).compile()
    assert kernel in chosen, f"{kernel} was never dispatched"
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{kernel} with {chosen[kernel]}: no Mosaic kernel")


INTERNLM2 = get_config("internlm2-1.8b")
GRANITE = get_config("granite-4.0-h-small-ep8")
# Each tuned kernel called through its ``pallas_call`` alone, inside a
# jitted function of another name, at the widths of its benchmark model.
UNWRAPPED = {
    "flash_attention": (
        "repro.kernels.flash_attention",
        lambda q, k, v: flash_attention_pallas.__wrapped__(
            q, k, v, num_q_heads=INTERNLM2.n_heads,
            num_kv_heads=INTERNLM2.n_kv_heads),
        [(INTERNLM2.n_heads, 1024, INTERNLM2.head_dim, BF),
         (INTERNLM2.n_kv_heads, 1024, INTERNLM2.head_dim, BF),
         (INTERNLM2.n_kv_heads, 1024, INTERNLM2.head_dim, BF)]),
    "ssd_scan": (
        "repro.kernels.ssd_scan",
        lambda x, dt, B, C, A: ssd_scan_pallas.__wrapped__(x, dt, B, C, A),
        [(_BH, 1024, _DH, BF), (_BH, 1024, F32), (_BH, 1024, _N, BF),
         (_BH, 1024, _N, BF), (_BH, F32)]),
    "moe_experts": (
        "repro.kernels.moe_experts",
        lambda x, w, sizes: moe_experts_pallas.__wrapped__(x, w, sizes),
        [(4096, GRANITE.d_model, BF),
         (GRANITE.experts_held, GRANITE.d_model, GRANITE.moe_d_ff, BF),
         (GRANITE.experts_held, jnp.int32)]),
}
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ", re.M)


@pytest.mark.parametrize("kernel", sorted(UNWRAPPED))
def test_kernel_op_keeps_its_name_unwrapped(kernel, one_chip, monkeypatch):
    module, fn, shapes = UNWRAPPED[kernel]
    monkeypatch.setattr(f"{module}.interpret_mode",
                        lambda requested=True: False)
    args = [jax.ShapeDtypeStruct(s[:-1], s[-1], sharding=one_chip)
            for s in shapes]

    def step(*xs):
        return fn(*xs)
    text = jax.jit(step).lower(*args).compile().as_text()
    names = _INSTRUCTION.findall(text)
    calls = [n for n, line in zip(names, _INSTRUCTION.split(text)[2::2])
             if "custom-call(" in line.split("\n", 1)[0]]
    assert any(re.search(KERNEL_OPS[kernel], n) for n in names), (
        f"no instruction matches {KERNEL_OPS[kernel]!r}; custom calls: "
        f"{calls}")


# The held experts' buffer in granite4h-fwd-8k: 4 x 8192 tokens, each with
# at most 9 of the chip's experts among its top 10.
_ROWS = 4 * 8192 * GRANITE.experts_held


@pytest.mark.parametrize("k,n", [(GRANITE.d_model, GRANITE.moe_d_ff),
                                 (GRANITE.moe_d_ff, GRANITE.d_model)])
def test_moe_experts_compiles_to_mosaic(k, n, one_chip, monkeypatch):
    """The ragged expert matmul at its fixed tiles, through the op, for the
    cell's gate/up and down projections."""
    monkeypatch.setattr(ops, "interpret_mode", lambda requested=True: False)
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((_ROWS, k), BF), ((GRANITE.experts_held, k, n), BF),
        ((GRANITE.experts_held,), jnp.int32))]
    compiled = jax.jit(lambda x, w, g: ops.moe_experts(
        x, w, group_sizes=g, use_pallas=True)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
