"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines:
  fig1  -- chosen vs exhaustive-optimal execution time (paper Fig. 1)
  table1-- chosen/best configs per kernel per size (paper Table I)
  fig3  -- system time: KLARAPTOR vs exhaustive search (paper Fig. 3)
  fig4  -- predicted-vs-actual trend alignment (paper Fig. 4)
  choose-- scalar vs vectorized driver choose() latency (BENCH_choose.json)
  search-- budgeted search-strategy quality vs exhaustive (BENCH_search.json)
  roofline -- three-term roofline per dry-run cell (assignment g), if
              dry-run artifacts exist
  telemetry -- closed-loop drift-detection/refit recovery
               (BENCH_telemetry.json); prints telemetry/skipped if the
               demo cannot run here
  introspect -- spec-extraction fidelity vs the hand-written tier-1 specs
              plus zero-hand-spec tuning of the auto kernels
              (BENCH_introspect.json); prints introspect/skipped if the
              demo cannot run here
  fleet -- distributed tuning farm: wall-clock speedup at 4 workers,
              kill/hang fault recovery with bit-identical merges, and the
              ledger->retune->cache pipeline (BENCH_fleet.json); prints
              fleet/skipped if the demo cannot run here
  serving -- the async continuous-batching front-end vs the sync engine
              (BENCH_serving.json); prints serving/skipped if the demo
              cannot run here
"""

from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (bench_choose_latency, bench_search,
                            fig1_accuracy, fig3_system_time, fig4_trends,
                            table1_configs)
    for mod in (fig1_accuracy, table1_configs, fig3_system_time,
                fig4_trends, bench_choose_latency):
        for line in mod.main():
            print(line, flush=True)
    # explicit empty argv: run.py's own flags must not leak into the
    # benchmark's --smoke mode (which sys.exits on gate failure)
    for line in bench_search.main([]):
        print(line, flush=True)
    try:
        from benchmarks import roofline_table
        for line in roofline_table.main():
            print(line, flush=True)
    except Exception as e:  # dry-run artifacts may not exist yet
        print(f"roofline/skipped,0,{e!r}", flush=True)
    # Trailing so a telemetry failure cannot mask the other benches; same
    # empty-argv pattern as bench_search (run.py's own flags must not leak
    # into --smoke, which sys.exits on gate failure).
    try:
        from benchmarks import bench_telemetry
        for line in bench_telemetry.main([]):
            print(line, flush=True)
    except Exception as e:  # missing telemetry artifacts / no cache dir
        print(f"telemetry/skipped,0,{e!r}", flush=True)
    # Trailing: introspection fidelity + auto-spec tuning must not mask the
    # benches above (and vice versa).
    try:
        from benchmarks import bench_introspect
        for line in bench_introspect.main([]):
            print(line, flush=True)
    except Exception as e:
        print(f"introspect/skipped,0,{e!r}", flush=True)
    # Trailing: tracing overhead must not mask the benches above (and
    # vice versa).
    try:
        from benchmarks import bench_trace
        for line in bench_trace.main([]):
            print(line, flush=True)
    except Exception as e:
        print(f"trace/skipped,0,{e!r}", flush=True)
    # Trailing: the tuning-farm drill (speedup, fault recovery, retune
    # pipeline) must not mask the benches above (and vice versa).
    try:
        from benchmarks import bench_fleet
        for line in bench_fleet.main([]):
            print(line, flush=True)
    except Exception as e:
        print(f"fleet/skipped,0,{e!r}", flush=True)
    # Trailing: the async-serving gates must not mask
    # the benches above (and vice versa).
    try:
        from benchmarks import bench_serving
        for line in bench_serving.main([]):
            print(line, flush=True)
    except Exception as e:
        print(f"serving/skipped,0,{e!r}", flush=True)
    # Trailing: the observatory gates (SLO closed loop, replay fidelity)
    # must not mask the benches above (and vice versa).
    try:
        from benchmarks import bench_obs
        for line in bench_obs.main([]):
            print(line, flush=True)
    except Exception as e:
        print(f"obs/skipped,0,{e!r}", flush=True)


if __name__ == "__main__":
    main()
