"""End-to-end serving driver: batched requests through the continuous-
batching engine (more requests than decode slots -> slots are recycled).

    PYTHONPATH=src python examples/serve_lm.py --requests 10 --batch 4

serves the full-width model with random weights; ``--smoke`` swaps in the
architecture's reduced config (a few seconds on a CPU).

``--async`` routes the same workload through the engine's async
front-end: a scheduler thread owns the device loop, ``submit`` is
thread-safe (requests here are submitted *while the engine is already
running*), and prefill advances in chunked jitted scans -- the prompt is
split into descending power-of-two chunks -- instead of one Python
round-trip per prompt token.  Greedy outputs are identical to the
synchronous path; the printout adds the compile counts, which stay at
one decode-step trace and at most log2(prefill_chunk)+1 prefill-chunk
traces regardless of how many distinct prompt lengths arrive.

``--trace out.json`` records the whole run (engine bring-up, prefill,
decode steps, kernel dispatch) as a nested span tree and writes a Chrome
trace-event file to load in ui.perfetto.dev.
"""

import argparse
import time

import jax

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.serve import build_engine
from repro.serving import Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the architecture's reduced config instead "
                         "of the full-width one")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--async", dest="run_async", action="store_true",
                    help="serve through the async front-end (scheduler "
                         "thread + chunked prefill); requests are "
                         "submitted while the engine is running")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome trace-event JSON of the run")
    args = ap.parse_args()

    use_compile_cache()
    tracer = None
    if args.trace:
        from repro.trace import Tracer
        tracer = Tracer()
    cfg = get_config(args.arch, smoke=args.smoke)
    engine = build_engine(cfg, args.batch, args.max_seq, trace=tracer)
    t0 = time.perf_counter()
    if args.run_async:
        # Submit-while-running: the scheduler thread picks requests up as
        # they arrive, which is the whole point of the async front-end.
        engine.start()
    for i in range(args.requests):
        prompt = [2 + (13 * i + j) % (cfg.vocab_size - 4)
                  for j in range(3 + i % 5)]
        engine.submit(Request(rid=i, prompt=prompt,
                              max_new_tokens=args.max_new,
                              temperature=0.0 if i % 2 == 0 else 0.8))
    if args.run_async:
        finished = engine.drain()
        engine.stop()
    else:
        finished = engine.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in finished)
    dev = jax.devices()[0]
    for r in sorted(finished, key=lambda r: r.rid):
        print(f"req {r.rid:2d} ({'greedy' if r.temperature == 0 else 'T=.8'})"
              f": {r.prompt} -> {r.output}")
    print(f"\n{len(finished)} requests, {toks} tokens in {dt:.1f}s through "
          f"{args.batch} continuous-batching slots "
          f"({toks / dt:.1f} tok/s on {dev.platform} {dev.device_kind}, "
          f"compilation included)")
    if args.run_async:
        cc = engine.compile_counts
        print(f"async front-end: {cc['decode_step']} decode-step "
              f"compile(s), {cc['prefill_chunk']} prefill-chunk compile(s) "
              f"across {args.requests} mixed-length prompts "
              f"(chunk={engine.prefill_chunk})")
    if tracer is not None:
        n = tracer.write_chrome_trace(args.trace)
        tracer.uninstall()
        print(f"trace: {n} spans -> {args.trace} (open in ui.perfetto.dev)")


if __name__ == "__main__":
    main()
