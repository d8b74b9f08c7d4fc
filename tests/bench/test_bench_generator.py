"""The traffic generator and the serving loop's records."""

import json
import math
import os
from statistics import NormalDist

import numpy as np
import pytest

from bench import generator

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "bench", "traffic", "offline-long-out.json")) as f:
    OFFLINE = json.load(f)
# A mix of the same kind in a seed-drawn order, with wider lognormals.
MIX = {"kind": "serve_offline",
       "prompt": {"median": 128, "sigma": 1.0, "min": 16, "max": 1024},
       "output": {"median": 64, "sigma": 1.0, "min": 8, "max": 512}}
SEED = 2 ** 33 + 17      # wider than 32 bits, as a run's seed may be


def test_same_seed_same_requests():
    a = generator.requests(MIX, SEED, 1000, 50)
    b = generator.requests(MIX, SEED, 1000, 50)
    assert a == b


def test_seeds_share_the_work_in_another_order():
    a = generator.requests(MIX, SEED, 1000, 200)
    b = generator.requests(MIX, SEED + 1, 1000, 200)
    for attr in ("max_new_tokens",):
        assert sorted(getattr(r, attr) for r in a) == \
            sorted(getattr(r, attr) for r in b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_fixed_order_keeps_the_lengths_in_place():
    mix = dict(MIX, fixed_order=True)
    a = generator.requests(mix, SEED, 1000, 100)
    b = generator.requests(mix, SEED + 1, 1000, 100)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_only_serving_mixes_make_requests():
    with pytest.raises(ValueError):
        generator.requests({"kind": "forward"}, SEED, 1000, 10)


@pytest.mark.parametrize("spec", [OFFLINE["prompt"], OFFLINE["output"],
                                  MIX["prompt"], MIX["output"]])
def test_lengths_follow_the_stated_lognormal(spec):
    n = 1000
    vals = generator.lognormal_set(spec, n)
    assert vals.min() >= spec["min"] and vals.max() <= spec["max"]
    assert abs(np.median(vals) - spec["median"]) <= 1
    dist = NormalDist(math.log(spec["median"]), spec["sigma"])
    for x in (spec["median"] / 2, spec["median"] * 2, spec["median"] * 4):
        if spec["min"] < x < spec["max"]:
            # Share at or below x against the lognormal's CDF: rounding to
            # whole tokens and the quantile grid, no more.
            share = float(np.mean(vals <= x))
            assert abs(share - dist.cdf(math.log(x + 0.5))) < 2 / n + 0.01


def test_forward_tokens_are_seeded_and_in_range():
    mix = {"kind": "forward", "pool": 2, "batch": 3, "seq_len": 16}
    a = generator.forward_tokens(mix, SEED, 50)
    assert a.shape == (2, 3, 16)
    assert (a == generator.forward_tokens(mix, SEED, 50)).all()
    assert a.min() >= generator.FIRST_TOKEN_ID and a.max() < 50
    assert not (a == generator.forward_tokens(mix, SEED + 1, 50)).all()


def test_offline_backlog_never_empties(smoke_cell):
    from bench.loops import serve_offline

    cell, base = smoke_cell("internlm2-1_8b", "serve_offline",
                            {"served_logit_gap": 1.0})
    loop = serve_offline.Loop(cell, 6, 2.0, "TPU v5 lite", lambda m: None,
                              base_config=base)
    try:
        rec = loop.window(1.0)
        assert loop.engine.pending
    finally:
        loop.release()
    assert rec["tokens"] > 0 and rec["compiles_in_window"] == 0
