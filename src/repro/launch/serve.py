"""Serving launcher: continuous-batching engine over a sharded decode step.

CPU-scale usage (smoke config, random weights -- demonstrates the engine,
the KV cache, and KLARAPTOR decode-launch decisions):

    python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --requests 8 --max-new 12

At start the engine warm-starts every tuned driver in the artifact cache;
each kernel launch then takes its config from a pinned override, else its
driver, else the op's default (kernels/ops.py).

``--telemetry`` opts into the runtime observability + drift-adaptive
retuning loop (repro.telemetry) over the tier-1 kernel specs and prints a
Prometheus-style metrics dump after the run; ``--telemetry-json PATH``
writes the full JSON snapshot instead.  ``--auto-kernels`` introspects and
tunes the auto-specced kernels (layernorm fusion, blocked column
reduction) and adds their specs to the telemetry.

``--trace out.json`` installs a repro.trace Tracer for the whole run and
writes a Chrome trace-event file at exit (open in ui.perfetto.dev);
``--ledger run.jsonl`` appends the flight ledger (choices, probes, drift,
refits -- implies --telemetry) for later replay with
``python -m repro.launch.status --ledger run.jsonl``.

After the run it prints the median and p90 time to first token, from
the engine's request stamps (submit to first token on the host).

``--async`` serves through the engine's async front-end (scheduler
thread, thread-safe submit, chunked jitted prefill -- see
serving/engine.py) and prints the compile counts afterwards.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.distributed.sharding import Sharder, decode_rules
from repro.launch.compile_cache import use_compile_cache
from repro.models import Model, init_params
from repro.serving import Request, ServingEngine

__all__ = ["main", "build_engine", "build_telemetry",
           "build_auto_kernels", "time_to_first_token_ms"]


def time_to_first_token_ms(requests) -> dict | None:
    """Median and p90 of submit-to-first-token, in ms, over the requests
    that emitted a token; ``None`` if none did."""
    ttft = [1e3 * (r.t_first - r.t_submit) for r in requests
            if r.t_first is not None and r.t_submit is not None]
    if not ttft:
        return None
    return {"median": float(np.median(ttft)),
            "p90": float(np.percentile(ttft, 90)), "requests": len(ttft)}


def build_auto_kernels(d_model: int = 1024, tune_device=None):
    """Introspect the auto-specced kernels (layernorm fusion + blocked
    column reduction) -- zero hand-written spec code.

    With ``tune_device`` (a DeviceModel) each kernel that has no registered
    or cached driver gets one built immediately (collect -> fit -> codegen,
    written through the artifact cache under the traced kernel's content
    hash); otherwise tuning is left to the cache warm start / lazy search.
    """
    from repro.introspect import auto_register
    from repro.kernels.layernorm import layernorm_grid_spec, layernorm_pallas
    from repro.kernels.reduce import colsum_grid_spec, colsum_pallas

    kernels = [
        auto_register(layernorm_pallas, layernorm_grid_spec(d_model)),
        auto_register(colsum_pallas, colsum_grid_spec()),
    ]
    if tune_device is not None:
        for ak in kernels:
            ak.ensure_driver(tune_device, repeats=2, max_configs_per_size=8)
    return kernels


def build_telemetry(seed: int = 0, auto_kernels=(), ledger=None):
    """Default serving telemetry: tier-1 kernel specs over the v5e oracle
    (plus any introspected auto-kernel specs).  ``ledger`` (path or
    repro.trace.Ledger) additionally appends every choice/probe/drift/refit
    to the JSONL flight ledger."""
    from repro.core import (V5eSimulator, flash_attention_spec, matmul_spec,
                            moe_gmm_spec, ssd_scan_spec)
    from repro.telemetry import Telemetry

    specs = [matmul_spec(), flash_attention_spec(), moe_gmm_spec(),
             ssd_scan_spec()] + [ak.spec for ak in auto_kernels]
    return Telemetry(specs, V5eSimulator(seed=seed), seed=seed,
                     ledger=ledger)


def build_engine(cfg, batch: int, max_seq: int, mesh=None, params=None,
                 seed: int = 0, telemetry=None, trace=None,
                 prefill_chunk: int = 32) -> ServingEngine:
    model = Model(cfg)
    sharder = Sharder(mesh=mesh, rules=decode_rules())
    if params is None:
        params = init_params(model.specs(), jax.random.PRNGKey(seed))
    return ServingEngine(model, params, sharder, batch=batch,
                         max_seq=max_seq, telemetry=telemetry,
                         trace=trace, prefill_chunk=prefill_chunk)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--telemetry", action="store_true",
                    help="install the runtime observability/retuning loop "
                         "and print its metrics after the run")
    ap.add_argument("--telemetry-json", metavar="PATH", default=None,
                    help="with --telemetry: write the JSON snapshot here "
                         "instead of printing Prometheus text")
    ap.add_argument("--auto-kernels", action="store_true",
                    help="introspect + tune the auto-specced kernels "
                         "(layernorm fusion, blocked column reduction) and "
                         "add their specs to the telemetry: zero "
                         "hand-written spec code")
    ap.add_argument("--async", dest="run_async", action="store_true",
                    help="serve through the async front-end (scheduler "
                         "thread + chunked prefill) instead of the "
                         "synchronous loop")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens advanced per jitted prefill scan "
                         "on the async path (default 32)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record structured spans for the whole run and "
                         "write a Chrome trace-event JSON here (open in "
                         "ui.perfetto.dev)")
    ap.add_argument("--ledger", metavar="PATH", default=None,
                    help="append the JSONL flight ledger (choices, probes, "
                         "drift, refits) here; implies --telemetry; replay "
                         "with python -m repro.launch.status --ledger PATH")
    ap.add_argument("--dash", metavar="PORT", type=int, default=None,
                    help="serve the live observatory dashboard (sparklines, "
                         "SLO state, accuracy scorecard) on this port for "
                         "the duration of the run; implies --telemetry")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    ledger = None
    if args.ledger:
        from repro.trace import Ledger
        ledger = Ledger(args.ledger)
    tracer = None
    if args.trace:
        from repro.trace import Tracer
        # The tracer shares the flight ledger, so completed spans persist
        # alongside choices/probes/drift/refits.
        tracer = Tracer(ledger=ledger)
    auto = []
    if args.auto_kernels:
        from repro.core import V5eSimulator
        auto = build_auto_kernels(d_model=cfg.d_model,
                                  tune_device=V5eSimulator())
        for ak in auto:
            print(f"auto kernel {ak.name}: "
                  f"{len(ak.spec.operands)} operands, "
                  f"grid rank {len(ak.spec.grid)}, "
                  f"constraints {list(ak.spec.constraints)}, "
                  f"kernel hash {ak.spec.source_fingerprint}")
    telemetry = (build_telemetry(auto_kernels=auto, ledger=ledger)
                 if args.telemetry or ledger is not None
                 or args.dash is not None else None)
    engine = build_engine(cfg, args.batch, args.max_seq, telemetry=telemetry,
                          trace=tracer, prefill_chunk=args.prefill_chunk)
    ws = engine.warm_started
    print(f"warm start: {len(ws)} driver(s) loaded {list(ws)}, "
          f"{ws.skipped_no_entry} without artifacts, "
          f"{ws.skipped_bad} unloadable")
    observatory = dash = None
    if args.dash is not None:
        # After engine construction so the observatory finds the installed
        # tracer (span sink) and the warm-start spans are already past.
        from repro.launch.dash import DashServer, DashState
        from repro.obs import Observatory
        observatory = Observatory(telemetry=telemetry,
                                  ledger=ledger).install()
        dash = DashServer(DashState(observatory, evaluate=True),
                          port=args.dash).serve_background()
        print(f"observatory dashboard on http://{dash.host}:{dash.port}/ "
              f"(metrics at /metrics)")
    for i in range(args.requests):
        prompt = [2 + (i * 7 + j) % (cfg.vocab_size - 3)
                  for j in range(4 + i % 4)]
        engine.submit(Request(rid=i, prompt=prompt,
                              max_new_tokens=args.max_new))
    finished = engine.run_async() if args.run_async else engine.run()
    for r in sorted(finished, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt={r.prompt} -> output={r.output}")
    ttft = time_to_first_token_ms(finished)
    if ttft is not None:
        print(f"time to first token: median {ttft['median']:.1f} ms, "
              f"p90 {ttft['p90']:.1f} ms over {ttft['requests']} requests")
    if args.run_async:
        cc, ip = engine.compile_counts, engine.cache_in_place
        print(f"async front-end: {cc['decode_step']} decode-step compile(s), "
              f"{cc['prefill_chunk']} prefill-chunk compile(s), "
              f"chunk={engine.prefill_chunk}; cache updated in place by "
              f"{ip['decode_step']} decode and {ip['prefill_chunk']} "
              f"prefill-chunk dispatch(es)")
    if observatory is not None:
        alerts = observatory.evaluate()
        firing = sorted({r for r, _ in observatory.slo.firing})
        print(f"observatory: {observatory.bus.n_events} events ingested, "
              f"{len(alerts)} alert transition(s) this tick, "
              f"firing: {firing or 'none'}")
        if dash is not None:
            dash.shutdown()
        observatory.uninstall()
    if telemetry is not None:
        if args.telemetry_json:
            with open(args.telemetry_json, "w") as f:
                f.write(telemetry.exporter.json())
            print(f"telemetry snapshot written to {args.telemetry_json}")
        elif args.telemetry:
            print(telemetry.prometheus(), end="")
        telemetry.uninstall()
    if tracer is not None:
        n = tracer.write_chrome_trace(args.trace)
        tracer.uninstall()
        print(f"trace: {n} spans written to {args.trace} "
              f"(open in ui.perfetto.dev)")
    if ledger is not None:
        ledger.close()
        print(f"flight ledger: {ledger.n_written} events appended to "
              f"{args.ledger}; render with "
              f"python -m repro.launch.status --ledger {args.ledger}")
        print(f"  (feed drift events to the tuning farm with "
              f"python -m repro.launch.fleet retune --ledger {args.ledger})")


if __name__ == "__main__":
    main()
