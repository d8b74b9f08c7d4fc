"""Serving benchmark on the CPU: the async continuous-batching front-end.

Two gates:

  * **async compile count** -- the serving engine's async front-end
    (scheduler thread + chunked jitted prefill) over >= 32 distinct
    prompt lengths: exactly one decode-step trace (prefill adds at most
    log2(prefill_chunk)+1 pow2-chunk traces, independent of how many
    prompt lengths arrive), with greedy outputs identical to the
    synchronous engine;
  * **async throughput** -- warm end-to-end tok/s of the async front-end
    >= 1.5x the synchronous engine on the same mixed-length,
    prefill-heavy workload (the async win is chunked prefill: one device
    dispatch per ``prefill_chunk`` prompt tokens instead of one Python
    round-trip per token).

Writes ``BENCH_serving.json`` (schema ``version: 1``) next to this file.

    PYTHONPATH=src python benchmarks/bench_serving.py            # full run
    PYTHONPATH=src python benchmarks/bench_serving.py --smoke    # CI gate

``--smoke`` exits non-zero if any gate fails.  The engine stages use a
deliberately tiny model config so host-side dispatch cost -- the thing
the async front-end removes -- is visible over device compute.  The
figures are CPU wall-clock times, not device measurements.
"""

from __future__ import annotations

import json
import os
import sys
import time

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")

COMPILE_COUNT_BAR = 1       # decode-step traces across the traffic mix
ASYNC_TOK_S_RATIO_BAR = 1.5  # async vs sync e2e tok/s
N_SHAPES_BAR = 32           # distinct request shapes each stage must cover


def _tiny_cfg():
    """A deliberately small decode config: device compute per step is a
    few hundred microseconds, so per-token Python dispatch -- the cost the
    async front-end's chunked prefill removes -- dominates the sync
    baseline the way a retrace would dominate production serving."""
    from repro.configs import get_config

    cfg = get_config("llama3.2-1b", smoke=True)
    return cfg.replace(n_layers=1, d_model=32, n_heads=1, n_kv_heads=1,
                       head_dim=32, d_ff=64, vocab_size=128,
                       logits_chunk=64)


def bench_async(batch: int = 4, max_seq: int = 96, max_new: int = 2,
                prefill_chunk: int = 32, repeats: int = 3) -> dict:
    from repro.core import registry
    from repro.launch.serve import build_engine
    from repro.serving import Request

    cfg = _tiny_cfg()
    # >= 32 distinct prompt lengths (all different -> 32+ distinct request
    # shapes through one compiled step), prefill-heavy vs max_new: the
    # async win is chunked prefill, so the workload keeps decode steps --
    # identical cost in both modes -- from diluting the ratio.
    lens = [17 + 2 * i for i in range(N_SHAPES_BAR)]
    assert lens[-1] + max_new < max_seq

    def prompts():
        return [[2 + (7 * i + j) % (cfg.vocab_size - 4) for j in range(L)]
                for i, L in enumerate(lens)]

    def one_mode(mode: str) -> tuple[dict, object]:
        registry.clear()
        engine = build_engine(cfg, batch, max_seq, seed=0,
                              prefill_chunk=prefill_chunk)
        run = engine.run if mode == "sync" else engine.run_async
        # Compile pass: trace the decode step and every pow2 prefill-chunk
        # size (a 2*prefill_chunk prompt splits into chunk, chunk/2, ..., 1)
        # so the timed passes measure only compiled steps.
        warm_lens = [2 * prefill_chunk] + lens[:batch - 1]
        for i, L in enumerate(warm_lens):
            p = [2 + (7 * i + j) % (cfg.vocab_size - 4) for j in range(L)]
            engine.submit(Request(rid=10_000 + i, prompt=p,
                                  max_new_tokens=2))
        run()
        # Best-of-N timed passes: scheduler noise on a shared host only
        # ever slows a pass down, so max tok/s is the stable statistic.
        best = None
        outputs = None
        for _ in range(repeats):
            engine.finished.clear()
            engine.cache = engine.model.init_cache(batch, max_seq)
            for i, p in enumerate(prompts()):
                engine.submit(Request(rid=i, prompt=list(p),
                                      max_new_tokens=max_new))
            t0 = time.perf_counter()
            finished = run()
            dt = time.perf_counter() - t0
            toks = sum(len(r.output) for r in finished if r.rid < 10_000)
            out = {r.rid: list(r.output) for r in finished if r.rid < 10_000}
            if outputs is None:
                outputs = out
            elif out != outputs:          # greedy passes must be identical
                outputs = {"mismatch": True}
            stats = {"tokens": toks, "wall_s": dt,
                     "tok_s": toks / max(dt, 1e-12)}
            if best is None or stats["tok_s"] > best["tok_s"]:
                best = stats
        best["outputs"] = outputs
        return best, engine

    sync_stats, _ = one_mode("sync")
    async_stats, engine = one_mode("async")

    outputs_equal = sync_stats.pop("outputs") == async_stats.pop("outputs")
    registry.clear()
    return {
        "batch": batch, "max_seq": max_seq, "max_new_tokens": max_new,
        "prefill_chunk": prefill_chunk,
        "n_prompt_lengths": len(set(lens)),
        "sync": sync_stats,
        "async": async_stats,
        "tok_s_ratio": async_stats["tok_s"] / max(sync_stats["tok_s"],
                                                  1e-12),
        "outputs_equal": bool(outputs_equal),
        "compile_counts": dict(engine.compile_counts),
    }


def run() -> dict:
    return {
        "version": 1,
        "compile_count_bar": COMPILE_COUNT_BAR,
        "async_tok_s_ratio_bar": ASYNC_TOK_S_RATIO_BAR,
        "n_shapes_bar": N_SHAPES_BAR,
        "engine": bench_async(),
    }


def main(argv=None) -> list[str]:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    report = run()
    if not smoke:
        with open(OUT_PATH, "w") as f:
            json.dump(report, f, indent=2)

    en = report["engine"]
    cc = en["compile_counts"]
    lines = [
        (f"serving/async,{en['async']['tok_s']:.1f},"
         f"sync_tok_s={en['sync']['tok_s']:.1f} "
         f"ratio={en['tok_s_ratio']:.2f}x "
         f"decode_compiles={cc['decode_step']} "
         f"prefill_compiles={cc['prefill_chunk']} "
         f"outputs_equal={en['outputs_equal']} "
         f"prompt_lengths={en['n_prompt_lengths']}"),
    ]

    failures = []
    if cc["decode_step"] != COMPILE_COUNT_BAR:
        failures.append(f"decode step compiled {cc['decode_step']}x across "
                        f"{en['n_prompt_lengths']} prompt lengths (want "
                        f"{COMPILE_COUNT_BAR})")
    if not en["outputs_equal"]:
        failures.append("async greedy outputs differ from sync engine")
    if en["tok_s_ratio"] < ASYNC_TOK_S_RATIO_BAR:
        failures.append(f"async tok/s ratio {en['tok_s_ratio']:.2f} < "
                        f"{ASYNC_TOK_S_RATIO_BAR:.2f} vs sync engine")
    if failures:
        lines.append(f"serving/FAIL,0,{'; '.join(failures)}")
        if smoke:
            for ln in lines:
                print(ln)
            sys.exit(1)
    return lines


if __name__ == "__main__":
    for ln in main():
        print(ln)
