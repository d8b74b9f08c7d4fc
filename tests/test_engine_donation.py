"""The serving engine's programs update the donated KV cache in place.

Both jitted programs alias their output cache to the buffers of the
cache they are given, so a step holds one cache and not two; the engine
counts the dispatches whose input cache the runtime consumed; one decode
trace still serves every prompt; and the faults the benchmark plants in
the decode step still change the served tokens through the donated path.
On the CPU at smoke size.
"""

import os
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch.serve import build_engine
from repro.models import Model
from repro.serving import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.join(REPO, "tests", "bench")):
    if path not in sys.path:
        sys.path.insert(0, path)
from test_bench_correct import _half_slots, _state_unchanged  # noqa: E402

CFG = get_config("internlm2-1.8b", smoke=True)
PROMPTS = [[3 + j for j in range(5)], [40 + 3 * j for j in range(9)]]


def _engine():
    return build_engine(CFG, batch=2, max_seq=32, prefill_chunk=4)


def _serve(engine) -> list:
    """Two requests through the async front end; their outputs."""
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(PROMPTS)]
    engine.start()
    try:
        for r in reqs:
            engine.submit(r)
        engine.drain(timeout=300)
    finally:
        engine.stop()
    assert all(r.done for r in reqs)
    return [list(r.output) for r in reqs]


def test_both_programs_alias_the_cache():
    engine = _engine()
    tok = jnp.asarray(engine.slot_last)
    pos = jnp.asarray(engine.slot_pos)
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    step = engine._step.lower(engine.params, tok, pos, engine.cache)
    prefill = engine._prefill_step.lower(
        engine.params, engine.cache, jnp.zeros(4, jnp.int32), i32(0),
        i32(0), i32(4), tok, pos)
    cache_bytes = sum(a.nbytes for a in jax.tree.leaves(engine.cache))
    for lowered in (step, prefill):
        aliased = lowered.compile().memory_analysis().alias_size_in_bytes
        assert aliased >= cache_bytes, (aliased, cache_bytes)


def test_every_dispatch_updates_the_cache_in_place():
    engine = _engine()
    calls = Counter()

    def counted(program, fn):
        def call(*args):
            calls[program] += 1
            return fn(*args)
        return call

    engine._step = counted("decode_step", engine._step)
    engine._prefill_step = counted("prefill_chunk", engine._prefill_step)
    _serve(engine)
    assert calls["decode_step"] > 0 and calls["prefill_chunk"] > 0
    assert engine.cache_in_place == dict(calls)
    assert engine.compile_counts["decode_step"] == 1


@pytest.mark.parametrize("fault", [_state_unchanged, _half_slots])
def test_a_decode_fault_still_changes_the_tokens(fault, monkeypatch):
    clean = _serve(_engine())
    fault(Model, monkeypatch)
    assert _serve(_engine()) != clean
