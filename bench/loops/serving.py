"""What the serving loops share: the program's ``ServingEngine`` built
through ``launch.serve.build_engine``, warmed up on every shape its
traffic uses, requests whose tokens are stamped as they are emitted, and
the comparison of served tokens with the plain reference.

A request's ``output`` is a ``TimedOutput``: the engine appends each
token to it once the token is on the host, and the append stamps the host
clock.  Time to first token, the gaps between tokens and the tokens
emitted in a window all come from those stamps.
"""

from __future__ import annotations

import time

import numpy as np

from bench import generator, harness

__all__ = ["TimedOutput", "ServingLoop"]


class TimedOutput(list):
    """A token list that stamps ``time.perf_counter()`` on each append."""

    def __init__(self):
        super().__init__()
        self.times: list[float] = []

    def append(self, token) -> None:
        self.times.append(time.perf_counter())
        super().append(token)


class ServingLoop:
    """Set-up and comparison of a serving cell; subclasses drive traffic."""

    def __init__(self, cell, seed: int, device_kind: str, log,
                 base_config=None):
        from repro.launch.serve import build_engine
        from repro.serving import Request

        self.cell, self.seed, self.log = cell, seed, log
        self.Request = Request
        mix, c = cell.traffic, cell.config
        self.pcfg = harness.program_config(c, cell.family, base_config)
        self.weights = harness.make_weights(c, cell.family, self.pcfg, seed)
        harness.build_drivers(self.pcfg, device_kind, log)
        with harness.Choices() as choices:
            self.engine = build_engine(
                self.pcfg, mix["slots"], mix["max_seq"],
                params=self.weights, seed=seed,
                prefill_chunk=mix["prefill_chunk"])
            self.engine.start()
            self._warm_up()
        for line in choices.lines():
            log(line)
        self.compiles_after_warmup = dict(self.engine.compile_counts)
        log(f"[warm-up] compiles {self.compiles_after_warmup}")

    def _warm_up(self) -> None:
        """One request whose prompt needs every prefill chunk length up to
        ``prefill_chunk``, then one decode step: every program the window
        can run is compiled."""
        chunk = self.cell.traffic["prefill_chunk"]
        prompt = list(range(generator.FIRST_TOKEN_ID,
                            generator.FIRST_TOKEN_ID + 2 * chunk))
        req = self.Request(rid=-1, prompt=prompt, max_new_tokens=2,
                           output=TimedOutput())
        self.engine.submit(req)
        while not req.done:
            time.sleep(0.01)

    def make_request(self, planned) -> object:
        return self.Request(rid=planned.rid, prompt=planned.prompt,
                            max_new_tokens=planned.max_new_tokens,
                            output=TimedOutput())

    def compiles_since_warmup(self) -> int:
        now = self.engine.compile_counts
        return sum(now[k] - self.compiles_after_warmup[k] for k in now)

    def release(self) -> None:
        """Stop the engine and free its cache and programs."""
        self.engine.stop()
        self.checked = self.pick_checked()
        del self.engine

    def pick_checked(self) -> list:
        """Requests to compare, drawn from the seed among those finished,
        the longest output always among them."""
        done = [r for r in self.candidates() if r.done and r.output]
        if not done:
            return []
        k = min(self.cell.traffic["check_requests"], len(done))
        longest = max(done, key=lambda r: len(r.output))
        rest = [r for r in done if r is not longest]
        rng = generator.rng_for(self.seed, "check")
        picks = rng.choice(len(rest), k - 1, replace=False) if k > 1 else []
        return [longest] + [rest[int(i)] for i in picks]

    def _reference_fn(self, quant):
        import jax
        import jax.numpy as jnp

        c, fam = self.cell.config, self.cell.family

        def gaps(w, tokens, positions, served):
            """Per served position: the reference's best logit, its logit
            for the served token, and its logit for the token that the
            precision ``quant`` (if any) would put first."""
            z = fam.reference_logits(c, w, tokens[None], positions)[0]
            best = z.max(-1)
            at_served = jnp.take_along_axis(z, served[:, None], -1)[:, 0]
            if quant is None:
                return best, at_served, at_served
            zq = fam.reference_logits(c, w, tokens[None], positions,
                                      quant=quant)[0]
            pick = zq.argmax(-1)
            at_pick = jnp.take_along_axis(z, pick[:, None], -1)[:, 0]
            return best, at_served, at_pick
        return jax.jit(gaps)

    def compare(self, quant: str | None = None) -> dict:
        """The widest gap by which a served token's reference logit lies
        below the reference's best, over every served token of the
        compared requests.  With ``quant`` the gap of the token that the
        reference in that precision puts first at each position: the
        control."""
        if not self.checked:
            return {"served_logit_gap": float("inf")}
        mix = self.cell.traffic
        seq, width = mix["max_seq"], mix["output"]["max"]
        fn = self._reference_fn(quant)
        worst = 0.0
        for r in self.checked:
            full = list(r.prompt) + list(r.output)
            tokens = np.zeros(seq, np.int32)
            tokens[:len(full)] = full
            n = len(r.output)
            pos = np.full(width, len(r.prompt) - 1, np.int32)
            pos[:n] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + n)
            served = np.zeros(width, np.int32)
            served[:n] = r.output
            best, at_served, at_pick = (np.asarray(a, np.float64)[:n]
                                        for a in fn(self.weights, tokens,
                                                    pos, served))
            chosen = at_served if quant is None else at_pick
            if not (np.isfinite(best).all() and np.isfinite(chosen).all()):
                return {"served_logit_gap": float("inf")}
            worst = max(worst, float((best - chosen).max()))
        self.log(f"[check] compared {sum(len(r.output) for r in self.checked)}"
                 f" served tokens of {len(self.checked)} requests"
                 f" (longest output {len(self.checked[0].output)})")
        return {"served_logit_gap": worst}
