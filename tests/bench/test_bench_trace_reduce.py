"""The reduction from a device trace to the per-layer metrics: on made-up
intervals, and on two-step excerpts of traces recorded on a TPU v5e (the
forward cells' traced windows, reduced by ``trace_reduce.load`` there)."""

import os

import pytest

from bench import harness, peaks, readers
from bench import trace_reduce as tr
from bench.run import Traced

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace(ops, programs=(), host=()):
    return tr.Trace(ops=[list(ops)], programs=[list(programs)],
                    host=list(host))


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union_intervals([(5, 7), (0, 2), (1, 3), (3, 4)]) == \
        [(0, 4), (5, 7)]


def test_busy_and_gaps_inside_the_window():
    t = _trace([["a", 0, 10], ["b", 5, 20], ["c", 30, 40], ["d", 95, 120]])
    assert tr.busy_ns(t, 0, 100) == 20 + 10 + 5
    assert tr.idle_gaps(t, 0, 100) == [(20, 30), (40, 95)]


def test_gaps_go_to_the_host_span_covering_most_of_them():
    t = _trace([["op", 0, 10], ["op", 50, 60]],
               host=[["main", "bench.window", 0, 60],
                     ["main", "outer", 5, 55],
                     ["main", "inner", 12, 48],
                     ["gen", "other", 10, 20]])
    # The gap (10, 50): inner covers 36 ns of it, outer 40 ns.
    assert tr.label_gaps(t, tr.idle_gaps(t, 0, 60)) == {"outer": 40}


def test_nested_operations_count_once_in_self_time():
    t = _trace([["while", 0, 100], ["body.a", 10, 40], ["body.b", 50, 90]])
    assert tr.op_ns(t, 0, 100) == {"while": 30, "body.a": 30, "body.b": 40}
    assert tr.busy_ns(t, 0, 100) == 100


def test_op_names_drop_the_hlo_text():
    assert tr.op_name("%fusion.12 = bf16[4,8]{1,0} fusion(...)") == \
        "fusion.12"
    assert tr.program_name("jit_step(1234)") == "jit_step"


def test_a_trace_without_a_tpu_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no TPU device plane"):
        tr.load(tr.find_xplane(str(tmp_path)))


def _recorded(cell_name):
    t = tr.Trace.from_json(os.path.join(DATA, f"{cell_name}.trace.json.gz"))
    cell = harness.load_cell(cell_name)
    return Traced(cell, None, {}, t, peaks.peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("cell_name,kernel,share", [
    ("internlm2-fwd-8k", "flash_attention", 0.40),
    ("mamba2-fwd-8k", "ssd_scan", 0.76)])
def test_recorded_forward_window(cell_name, kernel, share):
    t = _recorded(cell_name)
    # Two whole steps of one program, the device never idle between them
    # for more than a few hundred microseconds.
    assert t.window_ns > 1e9
    assert readers.idle_share(t) < 1.0
    assert list(t.programs) == [readers.FORWARD_PROGRAM]
    assert t.programs[readers.FORWARD_PROGRAM] == pytest.approx(
        t.busy_ns, rel=1e-3)
    assert readers.program_count(t, readers.FORWARD_PROGRAM) == \
        pytest.approx(2.0, abs=0.01)
    # The tuned kernel, found by name: one call per layer per step.
    calls = tr.op_events(t.trace, readers.KERNEL_OPS[kernel], t.t0, t.t1)
    assert len(calls) == 2 * 24
    spent = sum(e - s for _, s, e in calls)
    assert spent / t.busy_ns == pytest.approx(share, abs=0.03)
    roofline = readers.kernel_roofline(t, kernel)
    assert 0 < roofline < 100
    assert 0 < readers.forward_mfu(t) < 100


def test_recorded_rooflines_by_hand():
    t = _recorded("internlm2-fwd-8k")
    calls = tr.op_events(t.trace, readers.KERNEL_OPS["flash_attention"],
                         t.t0, t.t1)
    per_call = sum(e - s for _, s, e in calls) * 1e-9 / len(calls)
    # 4 * 128 * (8192 * 8193 / 2) * 4 rows * 16 heads FLOPs at 197 TFLOP/s.
    least = 4 * 128 * 8192 * 8193 / 2 * 64 / 197e12
    assert readers.kernel_roofline(t, "flash_attention") == \
        pytest.approx(100 * least / per_call)


def test_recorded_offline_serving_window():
    # An excerpt of the offline cell's traced window: the last two prefill
    # chunks of a prompt, then three decode steps, each followed by the
    # host's sampling.
    t = _recorded("internlm2-offline-decode")
    assert t.window_ns == pytest.approx(0.286e9)
    prefill = t.programs[readers.PREFILL_PROGRAM]
    decode = t.programs[readers.DECODE_PROGRAM]
    assert readers.program_runs(t, readers.PREFILL_PROGRAM) == \
        pytest.approx([0.134151466, 0.035415391])
    assert len(readers.program_runs(t, readers.DECODE_PROGRAM)) == 3
    assert readers.program_share(t, readers.PREFILL_PROGRAM) == \
        pytest.approx(100 * prefill / t.busy_ns)
    assert 60 < readers.program_share(t, readers.PREFILL_PROGRAM) < 66
    assert readers.decode_step_ms(t) == pytest.approx(1e-6 * decode / 3,
                                                      rel=1e-3)
    # Between decode steps the device waits on the host's sampling.
    assert 4 < readers.idle_share(t) < 8
    assert readers.kernel_roofline(t, "flash_attention") is None
