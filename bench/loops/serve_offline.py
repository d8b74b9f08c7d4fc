"""Offline batch generation through the engine's async front end.

The whole backlog is submitted before the engine starts on it, so every
slot refills as soon as it frees and the queue never empties.  The
window opens once ``warm_finished`` requests have finished, so the slots
hold requests of different ages, as in a long-running batch job.  The
metric is the tokens emitted in the window over its length.
"""

from __future__ import annotations

import time

from bench import generator
from bench.loops.serving import ServingLoop

__all__ = ["Loop"]


class Loop(ServingLoop):
    def __init__(self, cell, seed: int, seconds: float, device_kind: str,
                 log, base_config=None):
        super().__init__(cell, seed, device_kind, log, base_config)
        mix = cell.traffic
        planned = generator.requests(mix, seed, cell.config["vocab_size"],
                                     mix["backlog"])
        self.requests = [self.make_request(p) for p in planned]
        # The scheduler is held while the backlog is queued: a scheduler
        # that raced the submissions would fill its first slots from
        # whatever had arrived, and the whole run's schedule would differ
        # from run to run.
        self.engine.stop()
        for r in self.requests:
            self.engine.submit(r)
        self.engine.start()
        while sum(r.done for r in self.requests) < mix["warm_finished"]:
            time.sleep(0.01)

    def window(self, seconds: float) -> dict:
        w0 = time.perf_counter()
        time.sleep(seconds)
        w1 = time.perf_counter()
        compiles = self.compiles_since_warmup()
        tokens, active = 0, 0
        for r in self.requests:
            n = sum(w0 <= t < w1 for t in r.output.times)
            tokens += n
            active += n > 0
        if not self.engine.pending:
            raise RuntimeError("the backlog ran dry inside the window")
        self.window_bounds = (w0, w1)
        summary = {"tokens": tokens, "requests_served": active,
                   "finished_before_window_end": sum(
                       r.done and r.output.times[-1] < w1
                       for r in self.requests), "window_s": w1 - w0}
        return {"tokens": tokens, "elapsed_s": w1 - w0, "attempted": active,
                "failed": 0, "compiles_in_window": compiles,
                "summary": summary}

    def end_to_end(self, rec: dict) -> dict:
        return {"served_tokens_per_s": rec["tokens"] / rec["elapsed_s"]}

    def candidates(self) -> list:
        """Requests finished by the window's end."""
        w1 = self.window_bounds[1]
        return [r for r in self.requests
                if r.done and r.output.times[-1] < w1]
