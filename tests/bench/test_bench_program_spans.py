"""The serving-engine metrics read from the program's own spans: worked
by hand on made-up spans and device intervals, and on an excerpt of the
offline cell's traced window recorded on a TPU v5e."""

import os

import pytest

from bench import program_spans as ps
from bench import readers
from bench import trace_reduce as tr
from bench.run import Traced

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _traced(ops, window=(5, 95)):
    trace = tr.Trace(ops=[list(ops)], programs=[[]],
                     host=[["main", tr.WINDOW_SPAN, *window]])
    return Traced(None, None, {}, trace, None)


def _spans(*spans):
    return ps.Spans([["engine-scheduler", *sp] for sp in spans])


# Device busy over [0, 10], [20, 30] and [50, 100]; the window is
# [5, 95], so the device is idle over (10, 20) and (30, 50).
OPS = [["a", 0, 10], ["b", 20, 30], ["c", 50, 100]]


def test_sample_idle_share_by_hand():
    t = _traced(OPS)
    spans = _spans(
        ["engine.sample", 2, 12, {}],     # straddles the start: 2 ns idle
        ["engine.decode", 25, 60, {"active": 3, "slots": 4}],
        ["engine.sample", 28, 45, {}],    # 15 ns idle
        ["engine.sample", 60, 98, {}],    # straddles the end: busy
        ["engine.sample", 100, 120, {}])  # outside the window
    assert ps.sample_idle_share(t, spans) == pytest.approx(100 * 17 / 90)


def test_slot_utilization_by_hand():
    spans = _spans(
        ["engine.decode", 2, 8, {"active": 4, "slots": 4}],   # began before
        ["engine.decode", 40, 45, {"active": 3, "slots": 4}],
        ["engine.sample", 41, 44, {}],
        ["engine.prefill_chunk", 60, 70, {"slot": 1, "slots": 4,
                                          "lanes": 2}],
        # Begins in the window and ends past it: counted.
        ["engine.prefill_chunk", 94, 99, {"slot": 2, "slots": 4,
                                          "lanes": 1}],
        ["engine.decode", 95, 99, {"active": 1, "slots": 4}])  # at the end
    # (3 + 2 + 1) useful of (4 + 4 * 2 + 4 * 1) executed slot-steps.
    assert ps.slot_utilization(spans, 5, 95) == pytest.approx(37.5)


def test_a_program_without_spans_reads_nothing():
    t = _traced(OPS)
    for spans in (ps.Spans([]), _spans(["engine.decode", 100, 110,
                                        {"active": 1, "slots": 4}])):
        assert ps.sample_idle_share(t, spans) is None
        assert ps.slot_utilization(spans, t.t0, t.t1) is None


def test_spans_round_trip_through_json(tmp_path):
    spans = _spans(["engine.decode", 1.5, 7.0, {"active": 2, "slots": 4}])
    path = str(tmp_path / "spans.json.gz")
    spans.to_json(path)
    assert ps.Spans.from_json(path) == spans


def test_spans_load_from_a_cpu_profile(tmp_path):
    import jax

    from repro.trace import trace_span

    jax.profiler.start_trace(str(tmp_path))
    with trace_span("engine.decode", active=3, slots=4):
        with trace_span("engine.sample"):
            pass
    with trace_span("other.span", k=1):
        pass
    jax.profiler.stop_trace()
    spans = ps.load(tr.find_xplane(str(tmp_path)))
    assert [(name, a) for _, name, _, _, a in spans.spans] == [
        ("engine.decode", {"active": 3, "slots": 4}), ("engine.sample", {})]
    (_, _, s0, e0, _), (_, _, s1, e1, _) = spans.spans
    assert s0 <= s1 <= e1 <= e0


def test_recorded_offline_engine_spans():
    # An excerpt of the offline cell's traced window on a TPU v5e, cut
    # there and read by the same readers: the last two prefill dispatches
    # of a prompt (4 and 2 lanes), then three decode rounds of 16 active
    # slots, each with its sampling and emit.  The values are those the
    # chip printed for the excerpt.
    t = Traced(None, None, {}, tr.Trace.from_json(os.path.join(
        DATA, "internlm2-offline-decode.engine.trace.json.gz")), None)
    spans = ps.Spans.from_json(os.path.join(
        DATA, "internlm2-offline-decode.engine.spans.json.gz"))
    assert t.window_ns == pytest.approx(0.575356347e9)
    names = [name for _, name, *_ in spans.spans]
    assert names == ["engine.prefill_chunk"] * 2 + [
        "engine.decode", "engine.sample", "engine.emit"] * 3
    # (2 + 4 lanes + 3 * 16 active) of (16 * 6 + 3 * 16) slot-steps.
    assert ps.slot_utilization(spans, t.t0, t.t1) == pytest.approx(37.5)
    assert ps.sample_idle_share(t, spans) == pytest.approx(2.3414259823920913)
    # Most of the device's idle time lies inside the host's sampling.
    assert readers.idle_share(t) == pytest.approx(3.1569508348536557)
    assert readers.program_runs(t, readers.PREFILL_PROGRAM) == \
        pytest.approx([0.133967271, 0.077399056])
