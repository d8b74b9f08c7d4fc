"""Observatory benchmark on the CPU: the acting-SLO loop closes, and
replay is exact.

Two stages:

  * **closed loop** -- a serving process with a deliberately corrupted fit
    (v5p physics published under the v5e name, as in bench_telemetry) and
    a synthetic padding-waste regression (``bucket_step`` events written
    to the ledger and the bus) runs under a full Observatory
    (metrics bus + burn-rate SLO rules + scorecard + retune queue), with
    telemetry in monitoring-only mode (``refit_enabled=False``) so the
    *SLO path* -- not the telemetry loop's own reflex -- must drive the
    reaction: the drift-EWMA and padding-waste burn rules breach, the
    structured alerts land in the flight ledger, the breached key jumps
    to the head of ``RetuneQueue.pending()`` with its SLO boost, the
    farm-shaped refit runs from the queue head, and the scorecard's
    observed/predicted ratio returns inside the acceptance band;
  * **replay** -- the run's JSONL ledger replayed through
    ``replay_ledgers`` must rebuild the live bus ``snapshot_json()``
    bit-identically (same event dicts, same anchored wall times, same
    window rotation).

Writes ``BENCH_obs.json`` (schema ``version: 1``) next to this file.

    PYTHONPATH=src python benchmarks/bench_obs.py            # full run
    PYTHONPATH=src python benchmarks/bench_obs.py --smoke    # CI gate
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

from repro.core import (Klaraptor, V5E, V5P, V5eSimulator, choose_or_default,
                        matmul_spec, registry)
from repro.fleet import RetuneQueue
from repro.obs import Observatory, get_metrics_bus, replay_ledgers
from repro.search import SearchBudget
from repro.telemetry import Telemetry, TelemetryConfig
from repro.telemetry.drift import DriftEvent
from repro.trace import Ledger, read_ledger

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_PATH = os.path.join(HERE, "BENCH_obs.json")

INJECTED_WASTE = 0.75        # per-step padding waste; burn 0.75/0.35 > 2x
MAX_STEPS = 64               # serving launches before giving up on drift
REFIT_DEVICE_SECONDS = 5.0   # retune budget: enough to rebuild a fit whose
                             # *calibration* (not just its argmin) recovers,
                             # the scorecard's stricter bar

D_TARGET = {"m": 4096, "n": 4096, "k": 4096}
MM_DEFAULT = {"bm": 128, "bn": 512, "bk": 512}

def _inject_bucket_step(led, waste: float, kernel: str) -> None:
    """One synthetic padding-waste sample, into the ledger and the live
    bus alike (so the replay stage sees the same stream)."""
    event = {"type": "bucket_step", "hit": True, "waste": float(waste),
             "kernel": kernel, "t_ns": time.monotonic_ns()}
    led.append(event)
    get_metrics_bus().ingest(event)


def _corrupted_build(spec, seed: int):
    """A fit whose coefficients describe the wrong device (bench_telemetry's
    corruption): v5p physics published under the v5e name."""
    fake_hw = dataclasses.replace(V5P, name=V5E.name)
    wrong_sim = V5eSimulator(fake_hw, noise=0.04, seed=seed)
    kl = Klaraptor(wrong_sim, hw=fake_hw)
    return kl.build_driver(spec, repeats=2, max_configs_per_size=16,
                           seed=seed, register=True)


def bench_closed_loop(seed: int = 29) -> dict:
    """SLO breach -> ledger alert -> boosted queue head -> retune ->
    scorecard back in band; plus the bit-identical replay check."""
    spec = matmul_spec()
    sim = V5eSimulator(noise=0.04, seed=seed)
    workdir = tempfile.mkdtemp(prefix="klaraptor-bench-obs-")
    old_env = os.environ.get("KLARAPTOR_CACHE_DIR")
    os.environ["KLARAPTOR_CACHE_DIR"] = os.path.join(workdir, "cache")
    registry.clear()
    ledger_path = os.path.join(workdir, "run.jsonl")
    tel = None
    obs = None
    t_start = time.perf_counter()
    try:
        _corrupted_build(spec, seed)
        led = Ledger(ledger_path)
        queue = RetuneQueue(os.path.join(workdir, "queue.json"))
        # Monitoring-only telemetry: drift is *observed* but the loop does
        # not react -- the SLO engine must be the thing that acts.
        tel = Telemetry([spec], sim, seed=seed, ledger=led,
                        config=TelemetryConfig(
                            probe_every=2, refit_enabled=False,
                            refit_repeats=3,
                            refit_max_configs_per_size=32,
                            refit_budget=SearchBudget(
                                max_device_seconds=REFIT_DEVICE_SECONDS),
                        )).install()
        obs = Observatory(telemetry=tel, queue=queue).install()

        # Serve with the corrupted fit until drift is visible, injecting a
        # padding-waste regression alongside (two independent SLO signals).
        steps = 0
        for steps in range(1, MAX_STEPS + 1):
            choose_or_default(spec.name, D_TARGET, MM_DEFAULT)
            _inject_bucket_step(led, INJECTED_WASTE, spec.name)
            if tel.drift_events:
                break

        alerts = obs.evaluate()
        breached = sorted({a.slo for a in alerts if a.state == "breach"})
        pend = queue.pending()
        head_key, head_event = pend[0] if pend else (None, {})
        head_boost = (queue.state["pending"][head_key].get("boost")
                      if head_key else None)
        row_key = next(iter(obs.scorecard.rows), None)
        row = obs.scorecard.rows.get(row_key)
        ratio_corrupted = (row.calibration() or {}).get("p50") if row else None

        # Farm-shaped retune from the queue head (what a fleet worker does
        # with the same event; see fleet/worker.py).
        refit_ok = False
        if head_event:
            drift = DriftEvent(
                kernel=head_event.get("kernel", spec.name),
                hw_name=head_event.get("hw", V5E.name),
                bucket=tuple(), D=dict(head_event.get("D") or D_TARGET),
                config=dict(head_event.get("config") or MM_DEFAULT),
                rel_error_ewma=float(
                    head_event.get("rel_error_ewma", 0.0)),
                n_samples=int(head_event.get("n_samples", 0)),
                predicted_s=float(head_event.get("predicted_s", 0.0)),
                observed_s=float(head_event.get("observed_s", 0.0)))
            result = tel.refit_now(drift)
            refit_ok = bool(result and result.succeeded)
            queue.mark_done(head_key, {"succeeded": refit_ok})

        # Post-retune serving: the refit cleared the scorecard ring; fresh
        # shadow probes of the swapped-in fit must land back in band.
        for _ in range(MAX_STEPS):
            choose_or_default(spec.name, D_TARGET, MM_DEFAULT)
            _inject_bucket_step(led, 0.05, spec.name)
        post_alerts = obs.evaluate()
        row = obs.scorecard.rows.get(row_key)
        ratio_recovered = (row.calibration() or {}).get("p50") if row else None
        in_band = obs.scorecard.within_slo(row) if row else None

        tel.uninstall()
        obs.uninstall()
        led.close()

        events = read_ledger(ledger_path)
        ledger_alerts = [e for e in events if e["type"] == "alert"]
        replay = replay_ledgers(ledger_path)
        bit_identical = (obs.bus.snapshot_json()
                         == replay.bus.snapshot_json())
        return {
            "steps_to_drift": steps,
            "slo_breached": breached,
            "alerts_in_ledger": len(ledger_alerts),
            "queue_head": head_key,
            "queue_head_boost": head_boost,
            "refit_succeeded": refit_ok,
            "ratio_p50_corrupted": ratio_corrupted,
            "ratio_p50_recovered": ratio_recovered,
            "scorecard_in_band": in_band,
            "post_retune_transitions": [[a.slo, a.state]
                                        for a in post_alerts],
            "ledger_events": len(events),
            "replay_bit_identical": bit_identical,
            "wall_seconds": time.perf_counter() - t_start,
        }
    finally:
        if tel is not None:
            tel.uninstall()
        if obs is not None:
            obs.uninstall()
        registry.clear()
        shutil.rmtree(workdir, ignore_errors=True)
        if old_env is None:
            os.environ.pop("KLARAPTOR_CACHE_DIR", None)
        else:
            os.environ["KLARAPTOR_CACHE_DIR"] = old_env


def run(seed: int = 29) -> dict:
    return {
        "version": 1,
        "seed": seed,
        "injected_waste": INJECTED_WASTE,
        "loop": bench_closed_loop(seed=seed),
    }


def main(argv=None) -> list[str]:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    report = run()
    if not smoke:
        with open(OUT_PATH, "w") as f:
            json.dump(report, f, indent=2)
    lp = report["loop"]
    lines = [
        f"obs/closed_loop,{lp['wall_seconds'] * 1e6:.0f},"
        f"breached={'+'.join(lp['slo_breached']) or 'none'} "
        f"alerts={lp['alerts_in_ledger']} head={lp['queue_head']} "
        f"refit_ok={lp['refit_succeeded']} "
        f"ratio={lp['ratio_p50_corrupted'] if lp['ratio_p50_corrupted'] is not None else float('nan'):.3f}"
        f"->{lp['ratio_p50_recovered'] if lp['ratio_p50_recovered'] is not None else float('nan'):.3f} "
        f"in_band={lp['scorecard_in_band']}",
        f"obs/replay,{lp['ledger_events']},"
        f"bit_identical={lp['replay_bit_identical']} "
        f"ledger_events={lp['ledger_events']}",
    ]

    failures = []
    need = {"drift_ewma", "padding_waste"}
    if not need <= set(lp["slo_breached"]):
        failures.append(f"SLO rules {sorted(need - set(lp['slo_breached']))} "
                        f"did not breach (got {lp['slo_breached']})")
    if lp["alerts_in_ledger"] < 1:
        failures.append("no alert events landed in the flight ledger")
    if not lp["queue_head"] or not lp["queue_head"].startswith("matmul"):
        failures.append(f"breached key not at queue head "
                        f"(head={lp['queue_head']!r})")
    if not lp["refit_succeeded"]:
        failures.append("queue-head retune did not succeed")
    if lp["scorecard_in_band"] is not True:
        failures.append(
            f"scorecard ratio did not return within SLO after retune "
            f"(p50 {lp['ratio_p50_corrupted']} -> "
            f"{lp['ratio_p50_recovered']}, in_band="
            f"{lp['scorecard_in_band']})")
    if not lp["replay_bit_identical"]:
        failures.append("ledger replay did not reproduce the live series "
                        "bit-identically")
    if failures:
        lines.append(f"obs/FAIL,0,{'; '.join(failures)}")
        if smoke:
            for ln in lines:
                print(ln)
            sys.exit(1)
    return lines


if __name__ == "__main__":
    for ln in main():
        print(ln)
