"""Continuous-batching serving engine.

A fixed pool of ``batch`` decode slots shares one jit-compiled decode step
(so shapes never change).  Requests queue up; free slots are filled by
prefilling the prompt through the same decode step.  Finished sequences
(EOS or max_new_tokens) free their slot immediately -- the decode batch
never drains, which is the continuous-batching property.

Two front-ends share those slots:

* ``run()`` -- the synchronous baseline: the caller's thread alternates
  fill/decode, and prefill feeds the prompt token-by-token (one Python
  round-trip per prompt token).
* ``start()`` / ``submit()`` / ``drain()`` (or the ``run_async()``
  convenience) -- the async front-end: a scheduler thread owns the
  device loop, ``submit`` is thread-safe and wakes it, and prefill runs
  *chunked* -- jitted ``lax.scan``s advance the prompt in its descending
  power-of-two chunk split (cap ``prefill_chunk``), so the trace cache
  holds at most log2(prefill_chunk)+1 prefill shapes no matter how many
  prompt lengths arrive (slot, start position, and valid count are
  traced operands; tail lanes past the valid count idempotently rewrite
  the chunk's first position).
  The host only blocks on device results at sample boundaries
  (``_decode_once`` reading logits), so slot bookkeeping overlaps device
  execution.  ``compile_counts`` tracks traces of the decode and prefill
  steps -- the "one compiled step serves every shape" invariant is
  ``compile_counts["decode_step"] == 1`` across a whole traffic mix.

The scheduler's host phases are ``repro.trace`` spans, so a profiler
trace (or an installed ``Tracer``) shows which phase each device gap
falls in.  No span waits on the device; with neither sink on, each costs
a flag check.

  ============================  ===========================================
  span                          host phase (attributes)
  ============================  ===========================================
  ``engine.wait``               asleep on the condition with no work
  ``engine.prefill``            admitting one request to a slot (``rid``,
                                ``slot``, ``tokens`` prefilled, ``queued``
                                requests still pending)
  ``engine.prefill_chunk``      one prefill dispatch: ``lanes`` steps of
                                the ``slots``-wide decode step, each
                                advancing ``slot`` alone
  ``engine.decode``             one decode round over ``slots`` slots,
                                ``active`` of them holding a request
  ``engine.sample``             inside decode: key split, the greedy and
                                sampled tokens, their copy to the host
  ``engine.emit``               inside decode: per-slot bookkeeping
  ============================  ===========================================

Each ``Request`` carries its lifecycle on the host's ``perf_counter``
clock: ``t_submit`` (queued), ``t_admit`` (taken into a slot),
``t_first`` (first token on the host) and ``t_done`` (finished).

When the traced step dispatches Pallas kernels, the KLARAPTOR drivers pick
their launch parameters for the current shapes at trace time (once per
shape, kept in each driver's decision history) -- the serving-side face of
the paper's "optimal values ... for each kernel launch independently".  At
startup the engine warm-starts every tuned driver found in the persistent
artifact cache (core/cache.py), so a fleet of serving processes shares one
tuning run instead of each re-deriving launch parameters.  For shapes with
*no* cached driver, ``tune_for_shape`` runs a budget-aware online search
(repro.search) instead of falling back to static defaults forever.

Passing ``telemetry=`` (a ``repro.telemetry.Telemetry``) opts the engine
into runtime observability: every launch decision is counted, a sampled
subset is shadow-probed against the device oracle, and drivers whose
predictions drift from observed reality are refit and hot-swapped under a
hard probe budget.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device_model import V5E
from repro.core.driver import choose_or_default, warm_start_from_cache
from repro.serving.sampling import greedy, sample
from repro.trace import trace_span

__all__ = ["Request", "ServingEngine"]


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    output: list[int] = field(default_factory=list)
    done: bool = False
    # Lifecycle stamps, ``time.perf_counter()`` seconds, each set once.
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None
    t_done: float | None = None


class ServingEngine:
    def __init__(self, model, params, sharder, batch: int, max_seq: int,
                 eos_id: int = 1, seed: int = 0, warm_start: bool = True,
                 telemetry=None, trace=None, prefill_chunk: int = 32):
        self.model = model
        self.params = params
        self.sharder = sharder
        self.batch = batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.key = jax.random.PRNGKey(seed)
        # Opt-in structured tracing (repro.trace.Tracer): installed as the
        # process-wide tracer before warm start so the whole engine
        # bring-up lands in the flight recorder.  Same sharing contract as
        # telemetry below: one process-wide slot, the caller decides which
        # tracer wins.
        self.tracer = trace
        if trace is not None:
            trace.install()
        # Opt-in runtime observability (repro.telemetry.Telemetry): installed
        # as the process-wide choice listener before any launch decision so
        # every choose_or_default this engine triggers is recorded, shadow-
        # probed (sampled), and drift-checked.  The engine does not own the
        # loop -- several engines in one process share one listener slot, so
        # the caller decides which Telemetry wins.
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.install()
        # Load tuned drivers from the artifact cache so the first decode
        # step already launches with optimal parameters.  ``warm_started``
        # is the loaded-names list with coverage counts attached
        # (WarmStartSummary).
        from repro.core.driver import WarmStartSummary
        self.warm_started: WarmStartSummary = \
            warm_start_from_cache() if warm_start else WarmStartSummary()
        if telemetry is not None:
            telemetry.note_warm_start(self.warm_started)

        self.cache = model.init_cache(batch, max_seq)
        self.slot_req: list[Request | None] = [None] * batch
        self.slot_pos = np.zeros(batch, np.int32)      # next write position
        self.slot_last = np.zeros(batch, np.int32)     # last emitted token
        self.slot_budget = np.zeros(batch, np.int32)
        self.pending: list[Request] = []
        self.finished: list[Request] = []

        # Async front-end state: one condition variable guards the pending
        # and finished queues (submit from any thread wakes the scheduler;
        # drain sleeps on it until the engine goes idle).
        self.prefill_chunk = max(1, int(prefill_chunk))
        self._cv = threading.Condition()
        self._thread: threading.Thread | None = None
        self._running = False
        self._max_steps = 10_000

        # Trace counters: each key is bumped inside the corresponding jitted
        # function *body*, which executes once per trace -- so the value is
        # the compile count, held at 1 for the decode step (and at most
        # log2(prefill_chunk)+1 for the pow2-split prefill scans) across
        # arbitrary traffic mixes.
        self.compile_counts = {"decode_step": 0, "prefill_chunk": 0}

        def step(params, token, pos, cache):
            self.compile_counts["decode_step"] += 1
            return model.decode_step(params, token, pos, cache, sharder)

        self._step = jax.jit(step)

        def prefill_chunk_step(params, cache, tokens, slot, pos0, n_valid,
                               base_tok, base_pos):
            # One scan lane per chunk position.  slot/pos0/n_valid are
            # TRACED operands, so every (prompt length, slot, offset)
            # combination shares this single trace; lanes past n_valid
            # rewrite position pos0 with tokens[0] -- an idempotent
            # re-write of work lane 0 already did, chosen over masking the
            # step out so the scan body stays branch-free.
            self.compile_counts["prefill_chunk"] += 1

            def body(carry, xs):
                i, tok_i = xs
                valid = i < n_valid
                tok = base_tok.at[slot].set(
                    jnp.where(valid, tok_i, tokens[0]))
                ps = base_pos.at[slot].set(
                    jnp.where(valid, pos0 + i, pos0))
                _, carry = model.decode_step(params, tok, ps, carry, sharder)
                return carry, None

            idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
            cache, _ = jax.lax.scan(body, cache, (idx, tokens))
            return cache

        self._prefill_step = jax.jit(prefill_chunk_step)

    # -- public API -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request; thread-safe, wakes the scheduler if running."""
        req.t_submit = time.perf_counter()
        with self._cv:
            self.pending.append(req)
            self._cv.notify_all()

    def start(self) -> None:
        """Start the async scheduler thread (idempotent)."""
        if self._thread is not None:
            return
        self._running = True
        self._thread = threading.Thread(
            target=self._scheduler_loop, daemon=True, name="engine-scheduler")
        self._thread.start()

    def stop(self) -> None:
        """Stop the scheduler thread and join it."""
        with self._cv:
            self._running = False
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def drain(self, timeout: float | None = None) -> list[Request]:
        """Block until every submitted request has finished (or timeout)."""
        with self._cv:
            self._cv.wait_for(
                lambda: not self._running or not self._has_work(), timeout)
            return list(self.finished)

    def run_async(self, max_steps: int = 10_000) -> list[Request]:
        """Async-front-end analogue of ``run``: start, drain, stop."""
        self._max_steps = max_steps
        self.start()
        try:
            self.drain()
        finally:
            self.stop()
        return self.finished

    def tune_for_shape(self, spec, D, device, strategy="surrogate",
                       budget=None, hw=None) -> dict[str, int]:
        """Launch parameters for a shape with no cached driver.

        Delegates to ``choose_or_default``'s opt-in escalation: the
        warm-started/cached driver when one exists and fits, otherwise a
        budget-aware online search against ``device`` (memoized per
        (kernel, hw, shape, strategy fingerprint, budget fingerprint) in
        the driver registry, so a serving process never pays more than one
        bounded probe pass per shape *per search configuration* --
        switching strategies or raising the budget at runtime re-searches
        instead of being silently ignored).
        ``strategy`` and ``budget`` are repro.search knobs (default:
        surrogate search at ~25% of a one-repeat exhaustive pass); ``hw``
        defaults to the oracle's own hardware profile so feasibility and
        cache lookups match the device being probed.
        """
        hw = hw if hw is not None else getattr(device, "hw", V5E)
        miss = {"__untuned__": -1}
        cfg = choose_or_default(spec.name, D, miss, hw=hw, spec=spec,
                                device=device, strategy=strategy,
                                budget=budget)
        if cfg == miss:
            raise ValueError(
                f"no tuned or searchable config for {spec.name} at {D}")
        return cfg

    def run(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while (self.pending or any(r is not None for r in self.slot_req)) \
                and steps < max_steps:
            self._fill_slots()
            self._decode_once()
            steps += 1
        return self.finished

    # -- internals ---------------------------------------------------------------
    def _has_work(self) -> bool:
        return bool(self.pending) or any(r is not None for r in self.slot_req)

    def _scheduler_loop(self) -> None:
        """Async device loop: fill free slots (chunked prefill), decode,
        notify waiters; sleep on the condition variable when idle so a
        ``submit`` wakes it immediately."""
        steps = 0
        while True:
            with self._cv:
                if not self._running:
                    return
                if steps >= self._max_steps:
                    self._running = False
                    self._cv.notify_all()
                    return
                if not self._has_work():
                    self._cv.notify_all()
                    with trace_span("engine.wait"):
                        self._cv.wait(0.05)
                    continue
            self._fill_slots(chunked=True)
            self._decode_once()
            steps += 1
            with self._cv:
                self._cv.notify_all()

    def _fill_slots(self, chunked: bool = False) -> None:
        for s in range(self.batch):
            if self.slot_req[s] is not None:
                continue
            with self._cv:
                if not self.pending:
                    break
                req = self.pending.pop(0)
                queued = len(self.pending)
            req.t_admit = time.perf_counter()
            # prefill the prompt through the shared decode step
            with trace_span("engine.prefill", rid=req.rid, slot=s,
                            tokens=len(req.prompt) - 1, queued=queued):
                if chunked:
                    self._prefill_chunked(s, req.prompt)
                else:
                    for t_idx, tok in enumerate(req.prompt[:-1]):
                        self._single(s, tok, t_idx)
            self.slot_req[s] = req
            self.slot_pos[s] = len(req.prompt) - 1
            self.slot_last[s] = req.prompt[-1]
            self.slot_budget[s] = req.max_new_tokens

    @staticmethod
    def _pow2_chunks(n: int, cmax: int) -> list[int]:
        """Descending powers of two summing to ``n``, each <= ``cmax``.

        Log2-bucketed chunk lengths: the scan compute is exactly ``n``
        lanes -- no masked tail lanes re-running decode steps -- at the
        cost of at most ``log2(cmax) + 1`` distinct chunk shapes, each
        traced once for the life of the engine.
        """
        out = []
        c = 1
        while c * 2 <= max(1, cmax):
            c *= 2
        while n > 0:
            while c > n:
                c //= 2
            out.append(c)
            n -= c
        return out

    def _prefill_chunked(self, slot: int, prompt: list[int]) -> None:
        """Prefill ``prompt[:-1]`` in jitted ``lax.scan`` chunks.

        One device dispatch per chunk instead of one per token.  Chunk
        lengths are the descending power-of-two split of the prompt (cap
        ``prefill_chunk``), so any prompt length costs exactly its own
        lane count and the trace-cache holds at most log2(prefill_chunk)+1
        prefill shapes; slot/offset/valid-count are traced operands, so
        prompts never add traces beyond those sizes.  No host block here
        -- the cache stays on device and the next step's dispatch queues
        behind it.
        """
        toks = prompt[:-1]
        base_tok = np.array(self.slot_last, np.int32)
        base_pos = np.array(self.slot_pos, np.int32)
        t0 = 0
        for c in self._pow2_chunks(len(toks), self.prefill_chunk):
            buf = np.asarray(toks[t0:t0 + c], np.int32)
            self._run_prefill(buf, slot, t0, c, base_tok, base_pos)
            t0 += c

    def _run_prefill(self, tokens, slot, pos0, n_valid,
                     base_tok, base_pos) -> None:
        """One chunked-prefill dispatch (asynchronous)."""
        with trace_span("engine.prefill_chunk", slot=int(slot),
                        slots=self.batch, lanes=int(n_valid)):
            args = (self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(slot, jnp.int32),
                    jnp.asarray(pos0, jnp.int32),
                    jnp.asarray(n_valid, jnp.int32),
                    jnp.asarray(base_tok), jnp.asarray(base_pos))
            self.cache = self._prefill_step(*args)

    def _single(self, slot: int, token: int, pos: int) -> None:
        """One prompt token through the whole decode step: a one-lane
        prefill dispatch."""
        with trace_span("engine.prefill_chunk", slot=slot, slots=self.batch,
                        lanes=1):
            tok = np.array(self.slot_last, np.int32)
            ps = np.array(self.slot_pos, np.int32)
            tok[slot] = token
            ps[slot] = pos
            _, self.cache = self._step(self.params, jnp.asarray(tok),
                                       jnp.asarray(ps), self.cache)

    def _decode_once(self) -> None:
        active = [s for s in range(self.batch) if self.slot_req[s] is not None]
        if not active:
            return
        with trace_span("engine.decode", active=len(active),
                        slots=self.batch):
            logits, self.cache = self._step(
                self.params, jnp.asarray(self.slot_last),
                jnp.asarray(self.slot_pos), self.cache)
            with trace_span("engine.sample"):
                self.key, sub = jax.random.split(self.key)
                temps = {r.temperature for s, r in enumerate(self.slot_req)
                         if r is not None}
                greedy_tok = np.asarray(greedy(logits))
                sampled_tok = np.asarray(sample(logits, sub, temperature=max(
                    temps | {1.0})))
            with trace_span("engine.emit"):
                self._emit(active, greedy_tok, sampled_tok)

    def _emit(self, active: list[int], greedy_tok, sampled_tok) -> None:
        """Append each active slot's token, and free the slots whose
        request finished."""
        now = time.perf_counter()
        for s in active:
            req = self.slot_req[s]
            nxt = int(greedy_tok[s] if req.temperature <= 0.0
                      else sampled_tok[s])
            req.output.append(nxt)
            if req.t_first is None:
                req.t_first = now
            self.slot_pos[s] += 1
            self.slot_last[s] = nxt
            self.slot_budget[s] -= 1
            if (nxt == self.eos_id or self.slot_budget[s] <= 0
                    or self.slot_pos[s] >= self.max_seq - 1):
                req.done = True
                req.t_done = now
                with self._cv:
                    self.finished.append(req)
                    self.slot_req[s] = None  # freed: continuous batching
                    self._cv.notify_all()
