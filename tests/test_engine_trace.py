"""The serving engine's spans and request stamps, on the CPU at smoke size.

Under a JAX profiler every host phase of the scheduler lands in the
trace as a span with its attributes; with a ``Tracer`` installed the
engine still never waits on the device for a span's sake; each request
is stamped once per phase of its life, in order; and the engine's
programs keep the module names the benchmark's readers match.
"""

import os
import sys
import time
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch.serve import build_engine, time_to_first_token_ms
from repro.serving import Request
from repro.trace import Tracer, set_tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from bench import program_spans, trace_reduce  # noqa: E402
from bench.readers import DECODE_PROGRAM, PREFILL_PROGRAM  # noqa: E402

CFG = get_config("llama3.2-1b", smoke=True)
PROMPTS = [[3 + j for j in range(2 + 3 * i)] for i in range(4)]
STAMPS = ("t_submit", "t_admit", "t_first", "t_done")


@pytest.fixture(autouse=True)
def no_ambient_tracer():
    set_tracer(None)
    yield
    set_tracer(None)


def _engine():
    return build_engine(CFG, batch=2, max_seq=32, prefill_chunk=4)


def _serve(engine, mode: str, make=Request) -> list:
    reqs = [make(rid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(PROMPTS)]
    if mode == "sync":
        for r in reqs:
            engine.submit(r)
        engine.run()
        return reqs
    engine.start()
    try:
        time.sleep(0.2)             # the scheduler waits with no work
        for r in reqs:
            engine.submit(r)
        engine.drain(timeout=300)
    finally:
        engine.stop()
    return reqs


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_engine_spans_reach_the_profile(mode, tmp_path):
    engine = _engine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = _serve(engine, mode)
    finally:
        jax.profiler.stop_trace()
    assert all(r.done for r in reqs)
    spans = [sp[1:] for sp in program_spans.load(
        trace_reduce.find_xplane(str(tmp_path))).spans]
    names = {name for name, *_ in spans}
    expected = {"engine.prefill", "engine.prefill_chunk", "engine.decode",
                "engine.sample", "engine.emit"}
    if mode == "async":
        expected.add("engine.wait")
    assert names == expected
    by_name = {}
    for name, s, e, a in spans:
        by_name.setdefault(name, []).append((s, e, a))
    keys = {name: {k for _, _, a in by_name[name] for k in a}
            for name in by_name}
    assert keys["engine.prefill"] == {"rid", "slot", "tokens", "queued"}
    assert keys["engine.prefill_chunk"] == {"slot", "slots", "lanes"}
    assert keys["engine.decode"] == {"active", "slots"}
    assert keys["engine.sample"] == keys["engine.emit"] == set()
    if mode == "async":
        assert keys["engine.wait"] == set()
    # Every prompt token but the last is one prefill lane.
    assert sum(a["lanes"] for _, _, a in by_name["engine.prefill_chunk"]) \
        == sum(len(p) - 1 for p in PROMPTS)
    assert sorted(a["rid"] for _, _, a in by_name["engine.prefill"]) == \
        list(range(len(PROMPTS)))
    assert all(a["slots"] == 2 and 1 <= a["active"] <= 2
               for _, _, a in by_name["engine.decode"])
    # Each decode round holds one sample and then one emit.
    for s, e, _ in by_name["engine.decode"]:
        inside = [name for name, cs, ce, _ in spans
                  if s <= cs and ce <= e and name != "engine.decode"]
        assert inside == ["engine.sample", "engine.emit"]
    assert len(by_name["engine.sample"]) == len(by_name["engine.decode"])


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_a_tracer_adds_no_device_wait(mode, monkeypatch):
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    engine = _engine()
    with Tracer() as tracer:
        reqs = _serve(engine, mode)
    assert all(r.done for r in reqs)
    assert calls == []
    names = {s.name for s in tracer.spans()}
    assert "engine.decode" in names and "engine.prefill_chunk" in names
    assert "engine.step" not in names


class _CountedRequest(Request):
    """Counts every stamp the engine writes."""

    def __setattr__(self, name, value):
        if name in STAMPS and value is not None:
            counts = self.__dict__.setdefault("stamp_counts", Counter())
            counts[name] += 1
        super().__setattr__(name, value)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_request_stamps_are_ordered_and_set_once(mode):
    reqs = _serve(_engine(), mode, make=_CountedRequest)
    for r in reqs:
        assert r.done and r.output
        assert r.stamp_counts == Counter(STAMPS)
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done


def test_time_to_first_token_by_hand():
    reqs = [Request(rid=i, prompt=[2], t_submit=10.0, t_first=10.0 + d)
            for i, d in enumerate([0.1, 0.2, 0.3, 0.4, 0.5])]
    reqs.append(Request(rid=9, prompt=[2], t_submit=10.0))
    ttft = time_to_first_token_ms(reqs)
    assert ttft["requests"] == 5
    assert ttft["median"] == pytest.approx(300.0)
    assert ttft["p90"] == pytest.approx(460.0)
    assert time_to_first_token_ms(reqs[-1:]) is None


def test_engine_programs_keep_their_module_names():
    engine = _engine()
    tok = jnp.asarray(engine.slot_last)
    pos = jnp.asarray(engine.slot_pos)
    step = engine._step.lower(engine.params, tok, pos, engine.cache)
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    prefill = engine._prefill_step.lower(
        engine.params, engine.cache, jnp.zeros(4, jnp.int32), i32(0),
        i32(0), i32(4), tok, pos)
    for lowered, program in ((step, DECODE_PROGRAM),
                             (prefill, PREFILL_PROGRAM)):
        text = lowered.compile().as_text()
        assert text.startswith(f"HloModule {program},"), text[:80]


def test_async_outputs_equal_sync_from_one_decode_trace():
    """The async front-end (pow2-chunked prefill) gives the greedy
    outputs of the token-by-token sync loop, and one decode-step trace
    serves every prompt length."""
    outs = {}
    for mode in ("sync", "async"):
        engine = _engine()
        reqs = _serve(engine, mode)
        assert all(r.done for r in reqs)
        outs[mode] = [list(r.output) for r in reqs]
        assert engine.compile_counts["decode_step"] == 1
        if mode == "async":
            # chunk 4: at most log2(4) + 1 prefill traces
            assert 1 <= engine.compile_counts["prefill_chunk"] <= 3
    assert outs["sync"] == outs["async"]


class TestPow2Chunks:
    def test_exact_cover_and_bounds(self):
        from repro.serving.engine import ServingEngine

        for cmax in (1, 2, 8, 32, 64):
            allowed = {c for c in (1, 2, 4, 8, 16, 32, 64) if c <= cmax}
            for n in list(range(0, 70)) + [127, 128, 129, 1000]:
                chunks = ServingEngine._pow2_chunks(n, cmax)
                assert sum(chunks) == n, (n, cmax)
                assert all(c in allowed for c in chunks), (n, cmax)
                # descending, so at most one of each size below the cap:
                # the trace-cache bound log2(cmax)+1 plus repeats of cmax
                assert chunks == sorted(chunks, reverse=True), (n, cmax)
                below_cap = [c for c in chunks if c < cmax]
                assert len(below_cap) == len(set(below_cap)), (n, cmax)

    def test_trace_set_is_log_bounded(self):
        from repro.serving.engine import ServingEngine

        sizes = set()
        for n in range(1, 2000):
            sizes.update(ServingEngine._pow2_chunks(n, 64))
        assert sizes == {1, 2, 4, 8, 16, 32, 64}   # log2(64)+1 traces
