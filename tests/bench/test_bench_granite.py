"""The Granite 4.0-H cell: its configuration against the program, its work
counts by hand, its metrics' readers, and ``correct`` deciding as it
should at a size the CPU can run (the cell's loop and reference, with
the chip look skipped)."""

import json
import os
import time

import pytest

from bench import family_readers, harness, peaks
from bench import trace_reduce as tr
from bench.models import granite_hybrid as fam
from bench.run import run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite4h-fwd-8k"

# The published configuration at the program's smoke widths: one period of
# ten layers, 4 of 16 experts held, top 8.
SMOKE = dict(num_hidden_layers=10, hidden_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, attention_multiplier=1 / 32,
             intermediate_size=64, shared_intermediate_size=128,
             num_local_experts=4,
             published={"num_hidden_layers": 40, "num_local_experts": 16},
             num_experts_per_tok=8, vocab_size=512, mamba_n_heads=8,
             mamba_d_head=32, mamba_d_state=32)
TRAFFIC = {"kind": "forward", "batch": 2, "seq_len": 256, "pool": 2,
           "logit_positions": 8, "check_rows_per_batch": 2}
# At this size the program read 0.0117-0.0121 and the fp8 control
# 0.0817-0.0885 on four seeds.  With 3 of 8 experts chosen the program
# read up to 0.039: the last chosen expert's gate is large there, and a
# choice that bf16 rounding flips moves the logits by that much.
SMOKE_LIMIT = 0.03
SEED = 2 ** 33 + 3


def _config(**over) -> dict:
    with open(os.path.join(ROOT, "bench", "configs",
                           "granite-4_0-h-small.json")) as f:
        return dict(json.load(f), **over)


class FakeChip:
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {}


def test_forward_correct_and_control_fails():
    from repro.configs import get_config

    config = _config(**SMOKE)
    base = get_config(config["program_id"], smoke=True)
    cell = harness.Cell(name="smoke-granite", chips=1, config=config,
                        traffic=dict(TRAFFIC),
                        limits={"logit_err": SMOKE_LIMIT}, end_to_end=[],
                        per_layer=[])
    result, loop = run_cell(cell, SEED, 1.0, False, [FakeChip()],
                            time.perf_counter(), base_config=base)
    assert result["correct"], result["checks"]
    control = loop.compare(quant="fp8")["logit_err"]
    assert control > 3 * result["checks"]["logit_err"]["value"]
    assert control > SMOKE_LIMIT
    assert result["device"]["memory_peak_bytes"] == loop.program_bytes > 0


def test_cell_and_configuration_as_the_benchmark_states():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "forward"
    assert (cell.traffic["batch"], cell.traffic["seq_len"]) == (4, 8192)
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "forward_tokens_per_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "idle_share.forward", "ssd_scan_roofline.granite4h",
        "moe_experts_roofline.granite4h", "forward_mfu.granite4h"}
    c = cell.config
    assert c["reduced"] == ["num_hidden_layers", "num_local_experts"]
    assert (c["num_hidden_layers"], c["num_local_experts"]) == (10, 9)
    assert c["published"] == {"num_hidden_layers": 40,
                              "num_local_experts": 72}
    assert len(c["layer_types"]) == 40
    assert c["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + \
        ["mamba"] * 4
    # Every width as published.
    assert (c["hidden_size"], c["intermediate_size"],
            c["shared_intermediate_size"], c["num_experts_per_tok"],
            c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["vocab_size"]) == (4096, 768, 1536, 10, 128, 64, 128, 100352)


def test_program_is_the_configuration():
    """The registry's chip share is what the file states, size by size."""
    c = _config()
    pcfg = harness.program_config(c, fam)
    assert (pcfg.n_layers, pcfg.n_experts, pcfg.experts_held,
            pcfg.top_k) == (10, 72, 9, 10)
    assert pcfg.padded_vocab == pcfg.vocab_size == 100352
    with pytest.raises(ValueError, match="differs"):
        harness.program_config(dict(c, num_local_experts=8), fam)


def test_work_counts_by_hand():
    # 2 layers (Mamba-2, attention); hidden 8; 2 query heads over 1 KV
    # head of 4; Mamba-2 with 2 heads of 8 (expand 2), state 3; 6 of 12
    # experts of width 5 held, top 4; a shared expert of width 7.
    c = {"num_hidden_layers": 2, "layer_types": ["mamba", "attention"],
         "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "mamba_expand": 2,
         "mamba_n_heads": 2, "mamba_d_head": 8, "mamba_d_state": 3,
         "intermediate_size": 5, "shared_intermediate_size": 7,
         "num_local_experts": 6, "num_experts_per_tok": 4,
         "published": {"num_local_experts": 12}, "vocab_size": 10}
    batch, seq, tokens = 3, 6, 18
    rows = tokens * 4 * 6 / 12                       # 36 expected pairs
    assert fam.expected_rows(c, tokens) == rows
    calls = fam.kernel_calls(c, batch, seq)
    experts = calls["moe_experts"]
    assert experts.flops == 3 * 2 * 8 * 5 * rows
    # Three weight matrices of the 6 held experts, and each row read in and
    # written out once, bf16.
    assert experts.bytes == 2 * (3 * 6 * 8 * 5 + 2 * rows * 8)
    ssd = calls["ssd_scan"]
    assert ssd.flops == 5 * 3 * 8 * batch * seq * 2
    # in_proj 8 x (2*16 + 2*3 + 2), out_proj 16 x 8; wq 8x8, wk 8x4,
    # wv 8x4, wo 8x8; per layer a router 8x12 and a shared expert 3 x 8x7.
    mamba, attn = 8 * 40 + 16 * 8, 64 + 32 + 32 + 64
    dense = 8 * 12 + 3 * 8 * 7
    attention = 4 * 4 * (6 * 7 / 2) * batch * 2
    want = (2 * (mamba + attn + 2 * dense) * tokens
            + 2 * experts.flops + ssd.flops + attention
            + 2 * 8 * 10 * batch * 2)
    assert fam.forward_flops(c, batch, seq, 2) == pytest.approx(want)


def test_published_cell_work():
    """At the cell's sizes: 40,960 expected held-expert rows a layer,
    about 2.66 GFLOP a token, and the SSD at 512 batch-heads."""
    c = _config()
    assert fam.expected_rows(c, 4 * 8192) == 40960
    assert fam.forward_flops(c, 4, 8192, 8) / (4 * 8192) == pytest.approx(
        2.6586e9, rel=1e-4)
    calls = fam.kernel_calls(c, 4, 8192)
    v5e = peaks.peaks_for("TPU v5 lite")
    assert calls["moe_experts"].min_seconds(v5e)[1] == "compute"
    assert calls["ssd_scan"].min_seconds(v5e)[1] == "memory"


class _Traced:
    """What a metric reads, built by hand: a window of 2 s, two forward
    steps, and kernel calls of known device time."""

    def __init__(self, ops):
        self.cell = harness.load_cell(CELL)
        self.peaks = peaks.peaks_for("TPU v5 lite")
        self.t0, self.t1 = 0.0, 2e9
        self.window_ns = 2e9
        self.trace = tr.Trace(
            ops=[ops], host=[],
            programs=[[["jit_bench_forward", 0.0, 1e9],
                       ["jit_bench_forward", 1e9, 2e9]]])


def test_readers_by_hand():
    c = _config()
    calls = fam.kernel_calls(c, 4, 8192)
    v5e = peaks.peaks_for("TPU v5 lite")
    ssd_least = calls["ssd_scan"].min_seconds(v5e)[0]
    moe_least = calls["moe_experts"].min_seconds(v5e)[0]
    # 9 SSD calls of 20 ms; one MoE layer's 3 calls of 2 ms each.
    ops = [[f"ssd_scan_pallas.{i}", i * 1e8, i * 1e8 + 2e7]
           for i in range(9)]
    ops += [[f"moe_experts_pallas.{i}", 1.5e9 + i * 1e7,
             1.5e9 + i * 1e7 + 2e6] for i in range(3)]
    t = _Traced(ops)
    got = family_readers.kernel_roofline(t, "ssd_scan")
    assert got == pytest.approx(100 * ssd_least / 0.02)
    got = family_readers.kernel_roofline(t, "moe_experts",
                                         launches=fam.EXPERT_LAUNCHES)
    assert got == pytest.approx(100 * moe_least / 0.006)
    flops = fam.forward_flops(c, 4, 8192, 8)
    assert family_readers.forward_mfu(t) == pytest.approx(
        100 * 2 * flops / (2.0 * v5e["bf16_flops_per_s"]))


def test_readers_find_nothing_in_a_program_without_the_kernels():
    t = _Traced([["fusion.1", 0.0, 1e9]])
    t.trace.programs = [[]]
    assert family_readers.kernel_roofline(t, "moe_experts", 3) is None
    assert family_readers.kernel_roofline(t, "ssd_scan") is None
    assert family_readers.forward_mfu(t) is None
