"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, drivers, weights drawn on the device from the seed,
compiles, warm-up) is timed from the start of this process to the first
instant of the window.  The window then runs the cell's loop for
``--seconds``; nothing compiles inside it.  With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` the window runs
under JAX's profiler and the result carries the cell's per-layer metrics,
read from the device trace by ``bench/metrics/<metric>.py``.

After the window the peak device memory is read, the program's state is
freed, and what the window produced is compared with the plain reference
(``correct``).  The numbers compared are printed with their limits as the
last lines of standard error, and the result as the last line of standard
output.  Without a TPU, or with fewer chips than the cell asks for, the
run fails and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def load_metric(name: str):
    path = os.path.join(harness.BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Traced:
    """What a per-layer metric reads: the reduced trace of the window,
    the loop's own records, the cell and the chip's peaks."""

    def __init__(self, cell, loop, rec: dict, trace, peaks: dict):
        from bench import trace_reduce as tr

        self.cell, self.loop, self.rec, self.trace = cell, loop, rec, trace
        self.peaks = peaks
        self.t0, self.t1 = tr.window_of(trace)
        self.window_ns = self.t1 - self.t0
        self.busy_ns = tr.busy_ns(trace, self.t0, self.t1)
        self.programs = tr.program_ns(trace, self.t0, self.t1)
        self.ops = tr.op_ns(trace, self.t0, self.t1)


def breakdown(t: Traced) -> dict:
    from bench import trace_reduce as tr

    ops = sorted(t.ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = tr.label_gaps(t.trace, tr.idle_gaps(t.trace, t.t0, t.t1))
    gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, base_config=None):
    """One run of ``cell``: the result object, ``correct`` decided, and
    the loop, whose ``compare`` can still be called."""
    import jax

    from bench import peaks as peaks_mod
    from bench import trace_reduce as tr

    kind = devices[0].device_kind
    harness.lowerings()
    peaks = peaks_mod.peaks_for(kind) if trace else None
    if trace:
        # A traced window may be shorter: a trace of every step of a
        # serving window would be too large to read back in time.
        seconds = min(seconds, cell.traffic.get("trace_seconds", seconds))
    loop = cell.loop.Loop(cell, seed, seconds, kind, log,
                          base_config=base_config)
    trace_dir = os.path.join(harness.TRACE_DIR, cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # Host spans from the benchmark's annotations and JAX's runtime;
        # no Python function tracing, which would slow the host it watches.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    lowered = harness.lowerings()
    setup_s = time.perf_counter() - t_start
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        rec = loop.window(seconds)
    log(f"[window] {json.dumps(rec.get('summary', {}))}")
    log(f"[window] programs lowered inside the window: "
        f"{harness.lowerings() - lowered}")
    if "compiles_in_window" in rec:
        log(f"[window] engine compiles inside the window: "
            f"{rec['compiles_in_window']}")
    metrics: dict = {}
    result: dict = {"device": harness.device_record(devices)}
    if trace:
        jax.profiler.stop_trace()
        reduced = tr.load(tr.find_xplane(trace_dir))
        t = Traced(cell, loop, rec, reduced, peaks)
        result["device"]["busy_s"] = t.busy_ns * 1e-9
        result["device"]["window_s"] = t.window_ns * 1e-9
        for m in cell.per_layer:
            value = load_metric(m["name"])(t)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = breakdown(t)
    else:
        values = loop.end_to_end(rec)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    log(f"[setup] {setup_s:.3f} s from process start to the window")
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)
    # The runtime's peak leaves out the compiled program's temporaries;
    # a loop that knows its program's footprint reports it too.
    peak = max(in_use, getattr(loop, "program_bytes", 0))
    result["device"]["memory_peak_bytes"] = peak
    log(f"[device] peak_bytes_in_use on the fullest chip: {in_use}; "
        f"reported peak: {peak}")
    loop.release()
    readings = loop.compare()
    checks = {k: {"value": v, "limit": cell.limits.get(k)}
              for k, v in readings.items()}
    correct = bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return {"correct": correct, "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": metrics, **result,
            "checks": checks}, loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.prepare_process()
    try:
        devices = harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    log(f"[device] {json.dumps(harness.device_record(devices))}")
    result, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_START)
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
