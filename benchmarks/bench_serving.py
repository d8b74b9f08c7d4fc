"""Serving benchmark: one compiled step serving every shape, and the
async continuous-batching front-end.

Three gates (the PR-9 tentpole acceptance criteria):

  * **in-graph bucketed dispatch** -- a jitted matmul step over a
    ``BucketedDispatch`` (core/buckets.py + core/device_plan.py) fed
    >= 32 distinct raw shapes, padded to the bucket envelope with the
    raw dims as traced operands: exactly ONE trace, every sliced output
    allclose to the unpadded reference, and every bucket's gathered
    config bit-identical to the host driver's ``choose()``;
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
the async front-end removes -- is visible over device compute, matching
the regime the compile-count property actually protects in production
(where a retrace, not the matmul, is the catastrophic cost).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_serving.json")

COMPILE_COUNT_BAR = 1       # decode-step traces across the traffic mix
ASYNC_TOK_S_RATIO_BAR = 1.5  # async vs sync e2e tok/s
N_SHAPES_BAR = 32           # distinct request shapes each stage must cover


# ---------------------------------------------------------------------------
# Stage 1: in-graph bucketed dispatch on a real tuned driver.
# ---------------------------------------------------------------------------

def bench_in_graph(seed: int = 7) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import (BucketLattice, Klaraptor, V5eSimulator,
                            build_bucketed_dispatch, matmul_spec, pad_to,
                            registry)
    from repro.kernels.ops import matmul

    registry.clear()
    spec = matmul_spec()
    sim = V5eSimulator(noise=0.03, seed=seed)
    kl = Klaraptor(sim, cache=False)
    build = kl.build_driver(spec, repeats=2, max_configs_per_size=16,
                            register=True)
    driver = build.driver

    # VMEM-feasibility-derived lattice over the serving envelope; n/k kept
    # narrow so the >= 32 raw shapes exercise m-axis rounding and the
    # in-range/miss boundary rather than blowing up the padded volume.
    lat = BucketLattice.from_spec(
        spec, {"m": (64, 1024), "n": (256, 512), "k": (512, 512)},
        hw=driver.hw)
    default = {"bm": 128, "bn": 512, "bk": 512}
    disp = build_bucketed_dispatch(spec.name, lat, default, hw=driver.hw,
                                   cache=False)

    env = lat.envelope_shape()
    M, N, K = env["m"], env["n"], env["k"]
    traces = {"n": 0}

    @jax.jit
    def step(xp, yp, dims):
        traces["n"] += 1            # trace-time only: the compile counter
        return matmul(xp, yp, in_graph=disp, dims=dims)

    @jax.jit
    def decide(dims):
        idx, hit = disp.branch_index(dims)
        return idx, hit

    # >= 32 distinct raw shapes inside the envelope (shapes above the
    # lattice top cannot pad into the static envelope by construction;
    # the in-jit miss path is covered in tests/test_buckets.py).
    raw_shapes = []
    for i in range(18):
        raw_shapes.append((40 + 57 * i, 256 if i % 2 == 0 else 500, 512))
    for i in range(16):
        raw_shapes.append((97 + 53 * i, 512, 512))
    raw_shapes = sorted(set(raw_shapes))
    assert len(raw_shapes) >= N_SHAPES_BAR

    rng = np.random.default_rng(0)
    allclose = True
    graph_host_agree = True
    max_err = 0.0
    n_hits = 0
    for (m, n, k) in raw_shapes:
        x = (rng.standard_normal((m, k)) / np.sqrt(k)).astype(np.float32)
        y = rng.standard_normal((k, n)).astype(np.float32)
        xp = pad_to(jnp.asarray(x), (M, K))
        yp = pad_to(jnp.asarray(y), (K, N))
        dims = jnp.asarray([m, n, k], dtype=jnp.int32)
        out = np.asarray(step(xp, yp, dims))[:m, :n]
        err = float(np.max(np.abs(out - x @ y)))
        max_err = max(max_err, err)
        allclose &= bool(np.allclose(out, x @ y, rtol=1e-4, atol=1e-4))
        idx, hit = decide(dims)
        h_idx, h_hit = disp.host_index({"m": m, "n": n, "k": k})
        graph_host_agree &= (int(idx) == h_idx and bool(hit) == h_hit)
        n_hits += int(h_hit)

    # Bit-identity: every lattice bucket's gathered config must equal the
    # host driver's own choose() at the bucket shape (same margin).
    bit_identical = True
    n_checked = 0
    for bucket in lat.all_buckets():
        cfg, hit = disp.host_config(bucket)
        try:
            ref = driver.choose(bucket)
        except ValueError:
            bit_identical &= not hit     # infeasible bucket must miss
            continue
        bit_identical &= hit and cfg == {p: int(v) for p, v in ref.items()}
        n_checked += 1

    registry.clear()
    return {
        "kernel": spec.name,
        "n_shapes": len(raw_shapes),
        "n_hits": n_hits,
        "n_misses": len(raw_shapes) - n_hits,
        "n_buckets": lat.n_buckets,
        "n_branches": disp.n_branches,
        "n_buckets_checked": n_checked,
        "compiles": traces["n"],
        "allclose": bool(allclose),
        "max_abs_err": max_err,
        "graph_host_agree": bool(graph_host_agree),
        "bit_identical": bool(bit_identical),
    }


# ---------------------------------------------------------------------------
# Stage 2: async front-end vs synchronous engine.
# ---------------------------------------------------------------------------

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
        engine = build_engine(cfg, batch, max_seq, seed=0, step_plans=False,
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
        "in_graph": bench_in_graph(),
        "engine": bench_async(),
    }


def main(argv=None) -> list[str]:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    report = run()
    if not smoke:
        with open(OUT_PATH, "w") as f:
            json.dump(report, f, indent=2)

    ig = report["in_graph"]
    en = report["engine"]
    cc = en["compile_counts"]
    lines = [
        (f"serving/in_graph,{ig['compiles']},"
         f"shapes={ig['n_shapes']} hits={ig['n_hits']} "
         f"allclose={ig['allclose']} bit_identical={ig['bit_identical']} "
         f"graph_host_agree={ig['graph_host_agree']} "
         f"max_err={ig['max_abs_err']:.2e}"),
        (f"serving/async,{en['async']['tok_s']:.1f},"
         f"sync_tok_s={en['sync']['tok_s']:.1f} "
         f"ratio={en['tok_s_ratio']:.2f}x "
         f"decode_compiles={cc['decode_step']} "
         f"prefill_compiles={cc['prefill_chunk']} "
         f"outputs_equal={en['outputs_equal']} "
         f"prompt_lengths={en['n_prompt_lengths']}"),
    ]

    failures = []
    if ig["compiles"] != COMPILE_COUNT_BAR:
        failures.append(f"in-graph step compiled {ig['compiles']}x "
                        f"across {ig['n_shapes']} shapes (want "
                        f"{COMPILE_COUNT_BAR})")
    if not ig["allclose"]:
        failures.append(f"padded-bucket outputs not allclose to unpadded "
                        f"reference (max err {ig['max_abs_err']:.2e})")
    if not ig["bit_identical"]:
        failures.append("bucket configs not bit-identical to host choose()")
    if not ig["graph_host_agree"]:
        failures.append("in-graph branch index disagrees with host replay")
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
