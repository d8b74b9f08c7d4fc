"""Introspection benchmark: spec extraction latency + auto-spec tuning.

Three claims of the repro.introspect subsystem, measured end to end:

  * **fidelity** -- introspected tier-1 specs choose bit-identical launch
    configs to the hand-written specs (drivers built with identical probe
    settings) at representative shapes;
  * **latency** -- ``spec_from_kernel`` (two abstract traces + IR analysis)
    stays in interactive territory (milliseconds, measured per kernel);
  * **zero-hand-spec tuning** -- the two auto-specced kernels (layernorm
    fusion, blocked column reduction) go introspect -> collect/fit ->
    choose -> dispatch with no KernelSpec written anywhere, and land
    within ``RATIO_BAR`` of the exhaustive optimum.

Writes ``BENCH_introspect.json`` next to this file.

    PYTHONPATH=src python benchmarks/bench_introspect.py            # full
    PYTHONPATH=src python benchmarks/bench_introspect.py --smoke    # CI gate

``--smoke`` exits non-zero on any fidelity disagreement, any auto-kernel
selection ratio below the bar, or a dispatched config that disagrees
with the driver -- the loud-failure gate for introspection regressions.
The latencies are CPU wall-clock times; the ratios are against the
simulated v5e.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from repro.core import (Klaraptor, V5eSimulator, choose_or_default, registry,
                        selection_ratio)
from repro.introspect import spec_from_kernel
from repro.introspect.tier1 import tier1_pairs

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_introspect.json")

RATIO_BAR = 0.85          # auto-kernel chosen-vs-optimal time ratio
INTROSPECT_MS_BAR = 2000  # spec extraction latency per kernel

# Fidelity shapes per tier-1 kernel (sublane-aligned serving lattice).
FIDELITY_SHAPES = {
    "matmul_b16": [{"m": 1024, "n": 1024, "k": 1024},
                   {"m": 4096, "n": 2048, "k": 4096},
                   {"m": 128, "n": 8192, "k": 1024},
                   {"m": 8192, "n": 512, "k": 2048}],
    "flash_attn_d128_causal": [{"bh": 8, "sq": 1024, "skv": 1024},
                               {"bh": 32, "sq": 4096, "skv": 4096},
                               {"bh": 16, "sq": 2048, "skv": 8192},
                               {"bh": 64, "sq": 512, "skv": 2048}],
    "moe_gmm_b16": [{"e": 8, "g": 1024, "k": 2048, "n": 1024},
                    {"e": 4, "g": 4096, "k": 1024, "n": 2048},
                    {"e": 16, "g": 512, "k": 1024, "n": 1024},
                    {"e": 8, "g": 2048, "k": 2048, "n": 2048}],
    "ssd_scan_h64_n128": [{"bh": 8, "s": 2048, "chunkflops": 1},
                          {"bh": 16, "s": 8192, "chunkflops": 1},
                          {"bh": 64, "s": 65536, "chunkflops": 1},
                          {"bh": 32, "s": 16384, "chunkflops": 1}],
}

# Auto kernels: (label, builder of (fn, grid_spec), evaluation shapes).
AUTO_SHAPES = {
    "layernorm": [{"r": 4096}, {"r": 16384}],
    "colsum": [{"r": 8192, "c": 4096}, {"r": 2048, "c": 8192}],
}


def _auto_kernels():
    from repro.kernels.layernorm import layernorm_grid_spec, layernorm_pallas
    from repro.kernels.reduce import colsum_grid_spec, colsum_pallas
    return [("layernorm", layernorm_pallas, layernorm_grid_spec(1024)),
            ("colsum", colsum_pallas, colsum_grid_spec())]


def bench_fidelity(seed: int = 11) -> list[dict]:
    rows = []
    for fn, gs, hand in tier1_pairs():
        t0 = time.perf_counter()
        intro = spec_from_kernel(fn, gs)
        t1 = time.perf_counter()
        intro2 = spec_from_kernel(fn, gs)          # warm second run
        t_warm = time.perf_counter() - t1
        assert intro2.source_fingerprint == intro.source_fingerprint
        b_h = Klaraptor(V5eSimulator(noise=0.03, seed=seed),
                        cache=False).build_driver(
            hand, repeats=2, max_configs_per_size=12, register=False)
        b_i = Klaraptor(V5eSimulator(noise=0.03, seed=seed),
                        cache=False).build_driver(
            intro, repeats=2, max_configs_per_size=12, register=False)
        sim = V5eSimulator(noise=0.0, seed=0)
        agree = True
        for D in FIDELITY_SHAPES[hand.name]:
            th, ti = hand.candidates(D), intro.candidates(D)
            agree &= len(th) == len(ti) and all(
                np.array_equal(th[p], ti[p]) for p in th.params)
            agree &= np.array_equal(
                sim.true_time_batch(hand.traffic_table(D, th)),
                sim.true_time_batch(intro.traffic_table(D, ti)))
            agree &= b_h.driver.choose(D) == b_i.driver.choose(D)
        rows.append({
            "kernel": hand.name,
            "agree": bool(agree),
            "introspect_ms_cold": (t1 - t0) * 1e3,
            "introspect_ms_warm": t_warm * 1e3,
            "n_shapes": len(FIDELITY_SHAPES[hand.name]),
            "flops_per_point": intro.flops_per_point,
            "n_constraints": len(intro.constraints),
            "source_fingerprint": intro.source_fingerprint,
        })
    return rows


def bench_auto(seed: int = 11) -> list[dict]:
    from repro.introspect import auto_register

    registry.clear()
    rows = []
    for label, fn, gs in _auto_kernels():
        sim = V5eSimulator(noise=0.03, seed=seed)
        t0 = time.perf_counter()
        ak = auto_register(fn, gs)
        introspect_s = time.perf_counter() - t0
        build = Klaraptor(sim, cache=False).build_driver(
            ak.spec, repeats=2, max_configs_per_size=16)
        ratios = []
        for D in AUTO_SHAPES[label]:
            r = selection_ratio(ak.spec, sim, build.driver, D)
            ratios.append(r["ratio"])
        # Dispatch: the registered driver serves the op's launch config.
        D = AUTO_SHAPES[label][0]
        dispatch_agree = (choose_or_default(ak.name, D, ak.defaults)
                          == build.driver.choose(D))
        rows.append({
            "kernel": ak.name,
            "introspect_ms": introspect_s * 1e3,
            "min_ratio": min(ratios),
            "ratios": ratios,
            "dispatch_agree": bool(dispatch_agree),
            "probe_device_s": build.probe_device_seconds,
            "build_wall_s": build.build_wall_seconds,
            "n_operands": len(ak.spec.operands),
            "constraints": list(ak.spec.constraints),
        })
    registry.clear()
    return rows


def run(seed: int = 11) -> dict:
    fidelity = bench_fidelity(seed)
    auto = bench_auto(seed)
    return {
        "ratio_bar": RATIO_BAR,
        "introspect_ms_bar": INTROSPECT_MS_BAR,
        "seed": seed,
        "fidelity": fidelity,
        "auto": auto,
        "all_agree": all(r["agree"] for r in fidelity),
        "min_auto_ratio": min(r["min_ratio"] for r in auto),
        "all_dispatch_agree": all(r["dispatch_agree"] for r in auto),
        "max_introspect_ms": max(r["introspect_ms_cold"] for r in fidelity),
    }


def main(argv=None) -> list[str]:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    report = run()
    if not smoke:
        with open(OUT_PATH, "w") as f:
            json.dump(report, f, indent=2)
    lines = []
    for r in report["fidelity"]:
        lines.append(
            f"introspect/{r['kernel']},"
            f"{r['introspect_ms_cold'] * 1e3:.0f},"
            f"agree={r['agree']} warm_ms={r['introspect_ms_warm']:.0f}")
    for r in report["auto"]:
        lines.append(
            f"introspect/auto_{r['kernel']},"
            f"{r['introspect_ms'] * 1e3:.0f},"
            f"ratio={r['min_ratio']:.3f} "
            f"dispatch_agree={r['dispatch_agree']}")
    failures = []
    if not report["all_agree"]:
        failures.append("introspected tier-1 spec disagrees with hand spec")
    if report["min_auto_ratio"] < RATIO_BAR:
        failures.append(
            f"auto-kernel selection ratio {report['min_auto_ratio']:.3f} "
            f"< {RATIO_BAR}")
    if not report["all_dispatch_agree"]:
        failures.append("auto-kernel dispatch disagrees with driver")
    if report["max_introspect_ms"] > INTROSPECT_MS_BAR:
        failures.append(
            f"introspection took {report['max_introspect_ms']:.0f}ms "
            f"> {INTROSPECT_MS_BAR}ms")
    if failures:
        lines.append(f"introspect/FAIL,0,{'; '.join(failures)}")
        if smoke:
            for ln in lines:
                print(ln)
            sys.exit(1)
    return lines


if __name__ == "__main__":
    for ln in main():
        print(ln)
