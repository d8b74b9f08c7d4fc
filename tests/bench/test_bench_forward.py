"""What the forward loop compares, and the memory it reports."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import work
from bench.loops import forward

SEEDS = [0, 7, 2 ** 33 + 5]


@pytest.mark.parametrize("batch,pool,parts", [(2, 2, 2), (4, 2, 2),
                                              (16, 2, 2), (16, 1, 4)])
def test_check_rows_cover_every_part_of_every_batch(batch, pool, parts):
    mix = {"batch": batch, "pool": pool, "check_rows_per_batch": parts}
    size = batch // parts
    for seed in SEEDS:
        rows = forward.check_rows(mix, seed)
        assert rows == forward.check_rows(mix, seed)
        assert len(rows) == pool * parts
        for i in range(pool):
            got = sorted(r for b, r in rows if b == i)
            assert [r // size for r in got] == list(range(parts))


def test_check_rows_are_drawn_from_the_seed():
    mix = {"batch": 16, "pool": 2, "check_rows_per_batch": 2}
    drawn = {tuple(forward.check_rows(mix, s)) for s in range(20)}
    assert len(drawn) > 10


def test_program_bytes_hold_arguments_outputs_and_temporaries():
    x = jnp.ones((256, 256), jnp.float32)
    compiled = jax.jit(lambda a: (a @ a).sum(0)).lower(x).compile()
    got = forward.program_bytes(compiled)
    m = compiled.memory_analysis()
    assert got == (m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert got >= x.nbytes + 256 * 4


def test_program_bytes_without_an_analysis_is_zero():
    class NoAnalysis:
        def memory_analysis(self):
            return None
    assert forward.program_bytes(NoAnalysis()) == 0


@pytest.mark.parametrize("config,width", [("internlm2-1_8b", 509),
                                          ("mamba2-130m", 512)])
def test_program_runs_at_the_published_head_width(config, width, smoke_cell):
    from bench import harness

    cell, base = smoke_cell(config, "forward", {})
    # 509 stays 509 without padding, and is padded to 512 where the
    # configuration pads to a multiple of 16.
    cell.config["vocab_size"] = 509
    pcfg = harness.program_config(cell.config, cell.family, base)
    assert pcfg.vocab_size == work.logit_width(cell.config) == width


def test_logit_positions_end_on_the_last():
    pos = forward.logit_positions(256, 8)
    assert len(pos) == 8 and pos[-1] == 255
    assert np.all(np.diff(pos) == 32)
