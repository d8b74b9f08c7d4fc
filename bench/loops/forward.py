"""Closed loop of full-sequence forwards, the path on which the tuner's
kernels run: token ids -> ``transformer.embed_tokens`` ->
``transformer.forward`` (``use_pallas`` as the configuration states) ->
``transformer.unembed`` at positions spread over each row.

One step is one call of the compiled program on a (batch, seq_len) block
of token ids, ending in ``block_until_ready``.  The loop runs whole steps
until ``seconds`` have passed.  Afterwards the logits of rows drawn from
the seed, some in every part of every batch, are compared with the plain
reference over the same rows.
"""

from __future__ import annotations

import time

import numpy as np

from bench import generator, harness, work

__all__ = ["Loop", "logit_positions", "check_rows", "program_bytes"]


def logit_positions(seq_len: int, count: int) -> np.ndarray:
    """Evenly spaced positions, the last included: the last query alone
    sees no masked key, so a fault in the causal mask would miss it."""
    stride = seq_len // count
    return np.arange(stride - 1, seq_len, stride)[:count]


class Loop:
    def __init__(self, cell, seed: int, seconds: float, device_kind: str,
                 log, base_config=None):
        import jax

        from repro.distributed.sharding import Sharder

        self.cell, self.seed, self.log = cell, seed, log
        mix, c = cell.traffic, cell.config
        self.pcfg = harness.program_config(c, cell.family, base_config)
        self.weights = harness.make_weights(c, cell.family, self.pcfg, seed)
        harness.build_drivers(self.pcfg, device_kind, log)
        self.sharder = Sharder(mesh=None)
        self.positions = logit_positions(mix["seq_len"],
                                         mix["logit_positions"])
        self.tokens_host = generator.forward_tokens(mix, seed,
                                                    c["vocab_size"])
        self.tokens = [jax.device_put(t) for t in self.tokens_host]
        with harness.Choices() as choices:
            self.compiled = jax.jit(self.step_fn()).lower(
                self.weights, self.tokens[0]).compile()
        for line in choices.lines():
            log(line)
        self.launches = choices.events
        self.program_bytes = program_bytes(self.compiled)
        log(f"[device] the step program's arguments, outputs and "
            f"temporaries: {self.program_bytes} bytes")
        # Warm-up: every input block once, so the window starts steady.
        self.outputs = [jax.block_until_ready(self.compiled(self.weights, t))
                        for t in self.tokens]

    def step_fn(self):
        """The program under test, as one function of (weights, tokens)."""
        import jax.numpy as jnp

        from repro.models import transformer as T

        cfg, sharder = self.pcfg, self.sharder
        positions = jnp.asarray(self.positions)

        def bench_forward(params, tokens):
            x = T.embed_tokens(cfg, params, tokens)
            hidden, _ = T.forward(cfg, params, x, sharder)
            return T.unembed(cfg, params, hidden[:, positions])
        return bench_forward

    def window(self, seconds: float) -> dict:
        import jax

        pool = len(self.tokens)
        ends = []
        t0 = time.perf_counter()
        while not ends or ends[-1] - t0 < seconds:
            i = len(ends) % pool
            with jax.profiler.TraceAnnotation("bench.forward_step"):
                self.outputs[i] = jax.block_until_ready(
                    self.compiled(self.weights, self.tokens[i]))
            ends.append(time.perf_counter())
        steps, elapsed = len(ends), ends[-1] - t0
        step_s = np.diff([t0] + ends)
        mix = self.cell.traffic
        # Host-clock step times, so that a run that reads low shows
        # whether one step stalled or every step slowed.
        summary = {"steps": steps,
                   "step_s_min": float(step_s.min()),
                   "step_s_median": float(np.median(step_s)),
                   "step_s_max": float(step_s.max())}
        return {"steps": steps, "elapsed_s": elapsed,
                "tokens": steps * mix["batch"] * mix["seq_len"],
                "attempted": steps, "failed": 0, "summary": summary}

    def end_to_end(self, rec: dict) -> dict:
        return {"forward_tokens_per_s": rec["tokens"] / rec["elapsed_s"]}

    def release(self) -> None:
        """Keep the rows to compare on the host; free the program."""
        self.check_rows = check_rows(self.cell.traffic, self.seed)
        width = work.logit_width(self.cell.config)
        self.got = np.stack([np.asarray(self.outputs[i][r, :, :width])
                             for i, r in self.check_rows])
        del self.compiled, self.outputs, self.tokens

    def compare(self, quant: str | None = None) -> dict:
        """The program's logits against the f32 reference's, as the worst
        relative RMS error over the compared rows and positions.  With
        ``quant`` the reference in that precision stands in for the
        program: the control."""
        import jax
        import jax.numpy as jnp

        c, fam = self.cell.config, self.cell.family
        rows = jnp.asarray(np.stack([self.tokens_host[i][r]
                                     for i, r in self.check_rows]))
        pos = jnp.asarray(self.positions)

        def ref(q):
            return np.asarray(jax.jit(
                lambda w, t: fam.reference_logits(c, w, t, pos, quant=q))(
                    self.weights, rows), np.float64)

        if not hasattr(self, "want"):
            self.want = ref(None)
        want = self.want
        got = self.got.astype(np.float64) if quant is None else ref(quant)
        return {"logit_err": relative_error(got, want)}


def check_rows(mix: dict, seed: int) -> list[tuple[int, int]]:
    """(batch, row) pairs to compare: in every batch of the pool, one row
    drawn from the seed in each of ``check_rows_per_batch`` equal parts of
    the batch, so a fault in any part of any batch is always compared."""
    rng = generator.rng_for(seed, "check")
    parts = mix["check_rows_per_batch"]
    edges = np.linspace(0, mix["batch"], parts + 1).astype(int)
    return [(i, int(rng.integers(lo, hi)))
            for i in range(mix["pool"])
            for lo, hi in zip(edges[:-1], edges[1:])]


def program_bytes(compiled) -> int:
    """Device bytes the compiled program holds while it runs: arguments,
    outputs and temporaries, as its compiler accounts them (the runtime's
    ``peak_bytes_in_use`` leaves temporaries out)."""
    m = compiled.memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def relative_error(got: np.ndarray, want: np.ndarray) -> float:
    """max over (row, position) of ||got - want|| / ||want||, over the
    vocabulary; infinite where either side is not finite."""
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    return float(err.max())
