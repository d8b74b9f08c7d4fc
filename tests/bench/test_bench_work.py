"""The benchmark's needed-work counts against hand arithmetic."""

import inspect
import json
import os

import pytest

from bench import peaks, work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_flash_causal_gqa_by_hand():
    # batch 2, 4 query heads over 2 KV heads, 8 positions, head size 16.
    w = work.flash_attention(2, 4, 2, 8, 16)
    pairs = 8 * 9 // 2                      # 36 (query, key) pairs kept
    assert w.flops == 4 * 16 * pairs * 2 * 4
    # q and o at 4 heads, k and v at 2, bf16, each moved once.
    assert w.bytes == 2 * 2 * 8 * 16 * (4 + 4 + 2 + 2)


def test_flash_non_causal_counts_every_pair():
    w = work.flash_attention(1, 2, 2, 8, 16, causal=False)
    assert w.flops == 4 * 16 * 64 * 2


def test_ssd_recurrence_by_hand():
    # batch 2, 3 heads, 10 positions, head size 4, state 8.
    w = work.ssd_scan(2, 3, 10, 4, 8)
    assert w.flops == 5 * 8 * 4 * 2 * 10 * 3
    # x and y at 3*4 bf16 values, B and C at 8 bf16 values, dt at 3 f32,
    # per token.
    assert w.bytes == 2 * 10 * (2 * 12 * 2 + 2 * 8 * 2 + 3 * 4)


def test_forward_numerator_dense_by_hand():
    c = {"family": "dense_lm", "num_hidden_layers": 2, "hidden_size": 8,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
         "intermediate_size": 16, "vocab_size": 10}
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8, three 8x16 MLP matrices.
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    tokens = 3 * 6
    attn = 4 * 4 * (6 * 7 / 2) * 3 * 2      # per layer
    unembed = 2 * 8 * 10 * 3 * 2            # 2 positions per row
    want = 2 * per_layer * 2 * tokens + 2 * attn + unembed
    assert work.forward_step(c, 3, 6, 2) == pytest.approx(want)


def test_forward_numerator_mamba2_by_hand():
    c = {"family": "mamba2", "n_layer": 2, "d_model": 8, "expand": 2,
         "headdim": 4, "d_state": 3, "vocab_size": 10}
    # d_inner 16, 4 heads; in_proj 8 x (2*16 + 2*3 + 4), out_proj 16 x 8.
    per_layer = 8 * 42 + 16 * 8
    tokens = 2 * 5
    ssd = 5 * 3 * 4 * 2 * 5 * 4             # per layer
    unembed = 2 * 8 * 10 * 2 * 1
    want = 2 * per_layer * 2 * tokens + 2 * ssd + unembed
    assert work.forward_step(c, 2, 5, 1) == pytest.approx(want)


def test_full_size_counts_match_the_published_sizes():
    # internlm2-1.8b at 4 x 8192: ~99 TFLOP of matmuls and ~26 TFLOP of
    # causal attention a step.
    c = _config("internlm2-1_8b")
    flash = work.kernel_calls(c, 4, 8192)["flash_attention"]
    assert flash.flops * 24 == pytest.approx(26.4e12, rel=0.01)
    assert work.forward_step(c, 4, 8192, 8) == pytest.approx(125.4e12,
                                                             rel=0.01)
    m = _config("mamba2-130m")
    ssd = work.kernel_calls(m, 16, 8192)["ssd_scan"]
    assert ssd.min_seconds(peaks.peaks_for("TPU v5 lite"))[1] == "memory"


@pytest.mark.parametrize("fn", [work.flash_attention, work.ssd_scan,
                                work.forward_step, work.kernel_calls])
def test_no_tile_or_chunk_length_enters(fn):
    params = set(inspect.signature(fn).parameters)
    assert not params & {"bq", "bkv", "chunk", "tile", "block"}


@pytest.mark.parametrize("tiles", [{"bq": 128, "bkv": 128},
                                   {"bq": 512, "bkv": 2048},
                                   {"chunk": 256}, {"chunk": 2048}])
def test_counts_are_the_same_whatever_the_launch_config(tiles, monkeypatch):
    # Whatever tiles the tuner would pick, the needed work of the cell's
    # calls is the same: the counts read shapes from the configuration.
    import repro.kernels.ops as ops

    for name, default in (("FLASH_DEFAULT", {"bq", "bkv"}),
                          ("SSD_DEFAULT", {"chunk"})):
        if default <= set(tiles):
            monkeypatch.setattr(ops, name,
                                {k: tiles[k] for k in default})
    c = _config("internlm2-1_8b")
    m = _config("mamba2-130m")
    assert work.kernel_calls(c, 4, 8192)["flash_attention"].flops == \
        4 * 128 * (8192 * 8193 / 2) * 4 * 16
    assert work.kernel_calls(m, 16, 8192)["ssd_scan"].flops == \
        5 * 128 * 64 * 16 * 8192 * 24


@pytest.mark.parametrize("name,width", [("internlm2-1_8b", 92544),
                                        ("mamba2-130m", 50288)])
def test_head_width_is_the_published_padded_vocabulary(name, width):
    # mamba2-130m: vocab 50277 padded to a multiple of 16, as published.
    assert work.logit_width(_config(name)) == width


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
