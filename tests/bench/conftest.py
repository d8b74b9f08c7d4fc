"""Cells of the benchmark at a size the CPU can run: the same loops, the
program's smoke configurations, and configuration files cut to match."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SMOKE_SIZES = {
    "internlm2-1_8b": dict(num_hidden_layers=2, hidden_size=128,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=32, intermediate_size=256,
                           vocab_size=512),
    "mamba2-130m": dict(n_layer=2, d_model=128, vocab_size=512, d_state=32,
                        headdim=32),
}

SMOKE_TRAFFIC = {
    "forward": {"kind": "forward", "batch": 2, "seq_len": 256, "pool": 2,
                "logit_positions": 8, "check_rows_per_batch": 2},
    "serve_offline": {"kind": "serve_offline", "slots": 4, "max_seq": 128,
                      "prefill_chunk": 8,
                      "prompt": {"median": 8, "sigma": 0.8, "min": 4,
                                 "max": 32},
                      "output": {"median": 24, "sigma": 0.8, "min": 8,
                                 "max": 64},
                      "backlog": 2000, "warm_finished": 4,
                      "check_requests": 2},
}


class FakeChip:
    """Stands in for the TPU in ``run_cell``: the harness's look for a
    chip is skipped, and the device kind selects the v5e's parameters."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {}


@pytest.fixture
def smoke_cell():
    """``smoke_cell(config, kind, limits)`` -> (Cell, program base config)."""
    from bench import harness
    from repro.configs import get_config

    def make(config_name: str, kind: str, limits: dict):
        with open(os.path.join(ROOT, "bench", "configs",
                               f"{config_name}.json")) as f:
            config = dict(json.load(f), **SMOKE_SIZES[config_name])
        base = get_config(config["program_id"], smoke=True)
        cell = harness.Cell(name=f"smoke-{config_name}-{kind}", chips=1,
                            config=config, traffic=dict(SMOKE_TRAFFIC[kind]),
                            limits=limits, end_to_end=[], per_layer=[])
        return cell, base
    return make


@pytest.fixture
def fake_chip():
    return [FakeChip()]
