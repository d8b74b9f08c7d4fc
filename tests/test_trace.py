"""repro.trace tests: span nesting/attrs (including under threads), the
zero-cost disabled path, Chrome trace-event export validity, the flight
ledger round-trip against the live exporter, Prometheus histogram/label
hardening, the new registry gauges, the status CLI, and the refit causal
span tree."""

import json
import threading

import pytest

from repro.core import (Klaraptor, V5E, V5eSimulator, matmul_spec, registry)
from repro.core.driver import (ChoiceEvent, choose_or_default,
                               set_choice_listener)
from repro.search import SearchBudget
from repro.telemetry import (RefitController, Telemetry, TelemetryConfig,
                             shape_bucket)
from repro.telemetry.drift import DriftEvent
from repro.trace import (HISTOGRAM_BOUNDS_S, Ledger, NULL_SPAN, Tracer,
                         get_tracer, ledger_summary, read_ledger, set_tracer,
                         trace_span, traced, tracing)

D_SMALL = {"m": 1024, "n": 1024, "k": 1024}
MM_DEFAULT = {"bm": 128, "bn": 512, "bk": 512}


@pytest.fixture(autouse=True)
def no_ambient_tracer():
    """Every test starts and ends with tracing disabled: the exporter
    determinism tests elsewhere rely on the process-wide slot being
    empty."""
    set_tracer(None)
    yield
    set_tracer(None)


@pytest.fixture()
def clean(tmp_path, monkeypatch):
    monkeypatch.setenv("KLARAPTOR_CACHE_DIR", str(tmp_path / "cache"))
    registry.clear()
    set_choice_listener(None)
    yield str(tmp_path / "cache")
    set_choice_listener(None)
    registry.clear()


class TestSpans:
    def test_disabled_is_shared_noop(self):
        assert get_tracer() is None and not tracing()
        # Same object every call: the off path allocates nothing per span.
        s = trace_span("anything", k=1)
        assert s is NULL_SPAN and trace_span("other") is s
        with s as inner:
            assert inner.set(a=1) is inner     # attrs silently dropped

    def test_traced_decorator_disabled_is_passthrough(self):
        @traced("f")
        def f(x):
            return x + 1
        assert f(1) == 2
        with Tracer() as tr:
            assert f(2) == 3
        assert [s.name for s in tr.spans()] == ["f"]

    def test_nesting_depth_attrs_and_order(self):
        with Tracer() as tr:
            with trace_span("outer", kernel="mm") as o:
                with trace_span("inner"):
                    pass
                o.set(result=7)
        inner, outer = tr.spans()
        assert (outer.name, outer.depth) == ("outer", 0)
        assert (inner.name, inner.depth) == ("inner", 1)
        assert outer.attrs == {"kernel": "mm", "result": 7}
        # child completes first and fits inside the parent's window
        assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns

    def test_exception_closes_span_and_marks_error(self):
        with Tracer() as tr:
            with pytest.raises(ValueError):
                with trace_span("boom"):
                    raise ValueError("x")
            with trace_span("after"):
                pass
        boom, after = tr.spans()
        assert boom.attrs["error"] == "ValueError"
        assert after.depth == 0       # stack fully unwound by the raise

    def test_ring_is_bounded_but_counts_everything(self):
        with Tracer(capacity=4) as tr:
            for i in range(10):
                with trace_span(f"s{i}"):
                    pass
        assert tr.n_spans == 10
        assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]
        # histograms aggregate past the ring
        assert sum(h["count"] for h in tr.histograms().values()) == 10

    def test_threads_get_independent_stacks(self):
        barrier = threading.Barrier(4)
        with Tracer() as tr:
            def work(tag):
                barrier.wait()      # all four nest concurrently
                with trace_span("outer", tag=tag):
                    with trace_span("inner", tag=tag):
                        pass
            threads = [threading.Thread(target=work, args=(i,), name=f"w{i}")
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        spans = tr.spans()
        assert len(spans) == 8
        by_thread = {}
        for s in spans:
            by_thread.setdefault(s.thread_name, []).append(s)
        assert set(by_thread) == {f"w{i}" for i in range(4)}
        for group in by_thread.values():
            # each thread saw its own 0/1 nesting, never a neighbour's
            assert sorted(s.depth for s in group) == [0, 1]
            assert all(s.tid for s in group)

    def test_summary_ranked_by_cumulative_time(self):
        with Tracer() as tr:
            for _ in range(3):
                with trace_span("cheap"):
                    pass
            with trace_span("dear"):
                t0 = tr  # noqa: F841 -- just burn a little time
                sum(range(20000))
        rows = tr.summary()
        assert [r["name"] for r in rows] == ["dear", "cheap"]
        assert rows[1]["count"] == 3
        assert rows[0]["max_s"] >= rows[0]["mean_s"] > 0


def _profiled_events(trace_dir, prefix):
    """(name, stats) of each host-plane event whose name starts with
    ``prefix`` in the profile written under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


class TestProfilerSink:
    """While a JAX profiler records, spans land in its trace."""

    @pytest.mark.parametrize("with_tracer", [False, True])
    def test_span_reads_back_from_the_profile(self, tmp_path, with_tracer):
        import jax

        tracer = Tracer().install() if with_tracer else None
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace_span("probe.outer", active=16) as sp:
                sp.set(extra=3)
                with trace_span("probe.inner", label="x"):
                    pass

            @traced("probe.deco", n=2)
            def f():
                return 1
            assert f() == 1
        finally:
            jax.profiler.stop_trace()
            set_tracer(None)
        events = dict(_profiled_events(tmp_path, "probe."))
        assert events == {"probe.outer": {"active": 16, "extra": 3},
                          "probe.inner": {"label": "x"},
                          "probe.deco": {"n": 2}}
        if with_tracer:
            assert sorted(s.name for s in tracer.spans()) == \
                ["probe.deco", "probe.inner", "probe.outer"]
            outer = [s for s in tracer.spans() if s.name == "probe.outer"]
            assert outer[0].attrs == {"active": 16, "extra": 3}

    def test_error_reaches_the_profile(self, tmp_path):
        import jax

        jax.profiler.start_trace(str(tmp_path))
        try:
            with Tracer():
                with pytest.raises(ValueError):
                    with trace_span("probe.boom", k=1):
                        raise ValueError("x")
        finally:
            jax.profiler.stop_trace()
        assert _profiled_events(tmp_path, "probe.") == \
            [("probe.boom", {"k": 1, "error": "ValueError"})]

    def test_neither_sink_gives_the_null_span(self):
        from jax.profiler import TraceAnnotation

        assert not TraceAnnotation.is_enabled() and not tracing()
        assert trace_span("engine.decode", active=1) is NULL_SPAN


class TestChromeExport:
    def test_chrome_trace_schema_and_containment(self, tmp_path):
        with Tracer() as tr:
            with trace_span("parent", kernel="mm", cfg={"bm": 128}):
                with trace_span("child", obj=object()):
                    pass
        payload = tr.chrome_trace()
        # round-trips through strict JSON (the object() attr stringified)
        payload = json.loads(json.dumps(payload))
        events = payload["traceEvents"]
        meta = [e for e in events if e["ph"] == "M"]
        assert {m["name"] for m in meta} >= {"process_name", "thread_name"}
        xs = {e["name"]: e for e in events if e["ph"] == "X"}
        assert set(xs) == {"parent", "child"}
        for e in xs.values():
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        p, c = xs["parent"], xs["child"]
        assert p["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3
        assert p["args"]["cfg"] == {"bm": 128}
        out = tmp_path / "trace.json"
        assert tr.write_chrome_trace(out) == 2
        assert json.loads(out.read_text())["traceEvents"]


class TestHistogramsAndPrometheus:
    def test_bucket_counts(self):
        from repro.trace import SpanHistogram
        h = SpanHistogram()
        h.add(500)             # 0.5us -> first bucket (<= 1us)
        h.add(5_000_000)       # 5ms   -> <= 1e-2 bucket
        h.add(int(20e9))       # 20s   -> +Inf overflow
        assert h.counts[0] == 1 and h.counts[-1] == 1
        assert h.count == 3 and h.max_ns == int(20e9)

    def test_prometheus_span_histogram_lines(self, clean):
        tel = Telemetry({}, V5eSimulator())
        with Tracer():
            for _ in range(4):
                with trace_span("fit"):
                    pass
            text = tel.prometheus()
        assert "# TYPE klaraptor_span_duration_seconds histogram" in text
        buckets = [int(line.rsplit(" ", 1)[1])
                   for line in text.splitlines()
                   if line.startswith("klaraptor_span_duration_seconds_bucket"
                                      '{span="fit"')]
        assert len(buckets) == len(HISTOGRAM_BOUNDS_S) + 1   # incl. +Inf
        assert buckets == sorted(buckets)                    # cumulative
        assert buckets[-1] == 4
        assert 'le="+Inf"' in text
        assert 'klaraptor_span_duration_seconds_count{span="fit"} 4' in text

    def test_prometheus_without_tracer_has_no_span_section(self, clean):
        tel = Telemetry({}, V5eSimulator())
        assert "span_duration_seconds" not in tel.prometheus()
        assert "spans" not in tel.snapshot()

    def test_label_escaping_regression(self, clean):
        # A kernel name containing a quote and a backslash used to emit an
        # unparseable exposition line.
        evil = 'mm"42\\x'
        tel = Telemetry({}, V5eSimulator()).install()
        try:
            choose_or_default(evil, {"m": 8}, MM_DEFAULT)
        finally:
            tel.uninstall()
        text = tel.prometheus()
        line = next(l for l in text.splitlines()
                    if l.startswith("klaraptor_key_choices_total"))
        assert '\\"' in line and "\\\\" in line
        assert evil not in line          # raw quote/backslash never leaks
        # and the snapshot keeps the unescaped truth
        assert tel.snapshot()["keys"][0]["kernel"] == evil


class TestChoiceEventTimestamp:
    def test_t_ns_stamped_when_listener_installed(self, clean):
        seen = []
        set_choice_listener(seen.append)
        choose_or_default("matmul_b16", D_SMALL, MM_DEFAULT)
        assert seen and seen[0].t_ns is not None and seen[0].t_ns > 0
        # and a hand-built event defaults to None (stamping is the
        # listener path's job, not the dataclass's)
        assert ChoiceEvent(kernel="k", D={}, config={}, source="default",
                           predicted_s=None, hw_name=V5E.name).t_ns is None


class TestRegistryGauges:
    def test_generation_memo_and_invalidation_gauges(self, clean):
        tel = Telemetry({}, V5eSimulator()).install()
        try:
            registry.note_override("matmul_b16", V5E.name, D_SMALL,
                                   MM_DEFAULT)
            snap0 = tel.snapshot()
            assert snap0["gauges"]["decision_memo_entries"] == 0
            # an override decision is memoized -> the gauge moves
            choose_or_default("matmul_b16", D_SMALL, MM_DEFAULT)
            snap1 = tel.snapshot()
            assert snap1["gauges"]["decision_memo_entries"] == 1
            # a registry mutation drops the memo and counts the kill
            registry.invalidate_kernel("matmul_b16")
            snap2 = tel.snapshot()
            assert snap2["gauges"]["registry_generation"] > \
                snap1["gauges"]["registry_generation"]
            assert snap2["gauges"]["decision_memo_entries"] == 0
            assert snap2["counters"]["memo_invalidations"] == \
                snap1["counters"]["memo_invalidations"] + 1
        finally:
            tel.uninstall()
        text = tel.prometheus()
        assert "# TYPE klaraptor_registry_generation gauge" in text
        assert "# TYPE klaraptor_decision_memo_entries gauge" in text
        assert "# TYPE klaraptor_memo_invalidations counter" in text
        assert "# TYPE klaraptor_plan_invalidations counter" in text


def _run_telemetry_with_ledger(tmp_path, refit=False):
    """Drive the real loop (simulator oracle) with a ledger attached."""
    path = tmp_path / "run.jsonl"
    cfg = TelemetryConfig(probe_every=1, min_samples=2, drift_threshold=0.2,
                          ewma_alpha=1.0, refit_enabled=refit,
                          refit_budget=SearchBudget(max_executions=24),
                          refit_repeats=1, refit_max_configs_per_size=4)
    tel = Telemetry([matmul_spec()], V5eSimulator(seed=3), config=cfg,
                    cache=False, ledger=str(path))
    tel.install()
    try:
        for _ in range(4):
            # fabricated optimistic prediction -> rel error > threshold
            tel._on_choice(ChoiceEvent(
                kernel="matmul_b16", D=dict(D_SMALL),
                config=dict(MM_DEFAULT), source="driver",
                predicted_s=1e-9, hw_name=V5E.name))
    finally:
        tel.uninstall()
        tel.ledger.close()
    return tel, path


class TestLedger:
    def test_round_trip_matches_exporter(self, clean, tmp_path):
        tel, path = _run_telemetry_with_ledger(tmp_path)
        events = read_ledger(path)
        s = ledger_summary(events)
        snap = tel.snapshot()
        assert s["choices_total"] == snap["counters"]["choices_total"]
        assert s["by_type"]["probe"] == \
            snap["counters"]["shadow_probes_total"]
        assert len(s["drift_events"]) == \
            snap["counters"]["drift_events_total"] > 0
        assert s["kernels"]["matmul_b16"]["by_source"]["driver"] == 4
        key = f"matmul_b16 {V5E.name} {list(s['rel_error'])[0].split(' ', 2)[2]}"
        assert s["rel_error"][key]["probes"] == \
            snap["counters"]["shadow_probes_total"]
        assert s["rel_error"][key]["rel_error_ewma"] == pytest.approx(
            snap["keys"][0]["rel_error_ewma"])

    def test_refit_lines_and_torn_tail(self, clean, tmp_path):
        tel, path = _run_telemetry_with_ledger(tmp_path, refit=True)
        with open(path, "a") as f:
            f.write('{"type": "choice", "torn')   # killed mid-write
        events = read_ledger(path)
        s = ledger_summary(events)
        assert len(s["refits"]) == tel.counters.refits_total > 0
        # coalesced weighting: a synthetic n_coalesced choice counts fully
        extra = dict(next(e for e in events if e["type"] == "choice"))
        extra["n_coalesced"] = 64
        s2 = ledger_summary(events + [extra])
        assert s2["choices_total"] == s["choices_total"] + 64

    def test_mid_file_corruption_strict_vs_lenient(self, tmp_path):
        path = tmp_path / "bad.jsonl"
        path.write_text('{"type": "choice"}\nnot json\n{"type": "probe"}\n')
        # Default is lenient: corrupt mid-file lines are skipped (counted
        # in a warning), the good lines survive -- what the fleet's
        # drift-queue ingest relies on.
        events = read_ledger(path)
        assert [e["type"] for e in events] == ["choice", "probe"]
        with pytest.raises(json.JSONDecodeError):
            read_ledger(path, strict=True)
        # A torn *tail* is tolerated even in strict mode.
        path.write_text('{"type": "choice"}\n{"type": "torn')
        assert [e["type"] for e in read_ledger(path, strict=True)] == \
            ["choice"]

    def test_tracer_spans_reach_ledger(self, tmp_path):
        path = tmp_path / "spans.jsonl"
        with Ledger(path) as led:
            with Tracer(ledger=led):
                with trace_span("a", kernel="mm"):
                    with trace_span("b"):
                        pass
        events = read_ledger(path)
        # every open writes one wall<->monotonic session anchor first
        assert events[0]["type"] == "session"
        assert {"wall_ns", "mono_ns", "pid"} <= set(events[0])
        spans = [e for e in events if e["type"] == "span"]
        assert [(e["name"], e["depth"]) for e in spans] == \
            [("b", 1), ("a", 0)]
        assert spans[1]["attrs"] == {"kernel": "mm"}
        assert ledger_summary(events)["spans"]["a"]["count"] == 1


class TestRefitSpanTree:
    def test_refit_chain_is_one_causal_tree(self, clean):
        kl = Klaraptor(V5eSimulator(noise=0.02, seed=5), cache=False)
        ctl = RefitController(
            kl, TelemetryConfig(refit_budget=SearchBudget(max_executions=32),
                                refit_repeats=1,
                                refit_max_configs_per_size=4))
        drift = DriftEvent(kernel="matmul_b16", hw_name=V5E.name,
                           bucket=shape_bucket(D_SMALL), D=dict(D_SMALL),
                           config=dict(MM_DEFAULT), rel_error_ewma=0.8,
                           n_samples=4, predicted_s=1e-9, observed_s=1e-3)
        with Tracer() as tr:
            ctl.refit(matmul_spec(), drift)
        by_name = {s.name: s for s in tr.spans()}
        assert {"refit", "refit.search", "refit.fit", "refit.validate",
                "refit.swap"} <= set(by_name)
        parent = by_name["refit"]
        for child in ("refit.search", "refit.fit", "refit.validate",
                      "refit.swap"):
            s = by_name[child]
            assert s.depth == parent.depth + 1
            assert parent.t0_ns <= s.t0_ns <= s.t1_ns <= parent.t1_ns
        assert "succeeded" in parent.attrs
        assert "executions" in by_name["refit.search"].attrs


class TestStatusCLI:
    def test_renders_ledger(self, clean, tmp_path, capsys):
        from repro.launch.status import main
        _, path = _run_telemetry_with_ledger(tmp_path, refit=True)
        assert main(["--ledger", str(path)]) == 0
        out = capsys.readouterr().out
        assert "decisions by kernel and source" in out
        assert "matmul_b16" in out
        assert "drift and refits" in out

    def test_renders_snapshot(self, clean, tmp_path, capsys):
        from repro.launch.status import main
        tel = Telemetry({}, V5eSimulator()).install()
        try:
            choose_or_default("matmul_b16", D_SMALL, MM_DEFAULT)
        finally:
            tel.uninstall()
        path = tmp_path / "snap.json"
        path.write_text(tel.exporter.json())
        assert main(["--snapshot", str(path), "--top", "3"]) == 0
        out = capsys.readouterr().out
        assert "decisions by source" in out and "default" in out

    def test_requires_exactly_one_source(self):
        from repro.launch.status import main
        with pytest.raises(SystemExit):
            main([])
