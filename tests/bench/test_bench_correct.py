"""``correct`` comes out false when it should: for the control (the plain
reference in fp8 in the program's place) and for faults planted in the
timed path underneath an otherwise whole run.  At the program's smoke
sizes on the CPU, with the chip look skipped (``fake_chip``)."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench.run import run_cell

# Limits at the smoke sizes, between the program's readings and the
# control's there (about 0.008-0.01 against 0.08-0.13 for the logits, and
# under 0.01 against 0.2-0.5 for the served gap).
SMOKE_LIMITS = {"logit_err": 0.03, "served_logit_gap": 0.1}
SEED = 2 ** 33 + 3


def _run(cell, base, chips, seconds=1.0):
    return run_cell(cell, SEED, seconds, False, chips, time.perf_counter(),
                    base_config=base)


CONFIGS = ["internlm2-1_8b", "mamba2-130m"]


@pytest.mark.parametrize("config", CONFIGS)
def test_forward_correct_and_control_fails(config, smoke_cell, fake_chip):
    cell, base = smoke_cell(config, "forward", SMOKE_LIMITS)
    result, loop = _run(cell, base, fake_chip)
    assert result["correct"], result["checks"]
    control = loop.compare(quant="fp8")["logit_err"]
    assert control > 3 * result["checks"]["logit_err"]["value"]
    assert control > SMOKE_LIMITS["logit_err"]
    # The fake chip reports no runtime peak: the program's own footprint
    # stands, and it holds at least the weights.
    weights = sum(x.nbytes for x in jax.tree.leaves(loop.weights))
    assert result["device"]["memory_peak_bytes"] == loop.program_bytes
    assert loop.program_bytes > weights


def _half_batch(step_fn):
    def broken(self):
        fn = step_fn(self)

        def half(params, tokens):
            out = fn(params, tokens[: tokens.shape[0] // 2])
            return jnp.concatenate([out, out], axis=0)
        return half
    return broken


def _answer_altered(step_fn):
    def broken(self):
        fn = step_fn(self)

        def altered(params, tokens):
            z = fn(params, tokens)
            return z.at[:, 0].set(jnp.roll(z[:, 0], 1, axis=-1))
        return altered
    return broken


@pytest.mark.parametrize("fault", [_half_batch, _answer_altered])
@pytest.mark.parametrize("config", CONFIGS)
def test_forward_fault_is_not_correct(config, fault, smoke_cell, fake_chip,
                                      monkeypatch):
    from bench.loops import forward

    monkeypatch.setattr(forward.Loop, "step_fn",
                        fault(forward.Loop.step_fn))
    cell, base = smoke_cell(config, "forward", SMOKE_LIMITS)
    result, _ = _run(cell, base, fake_chip)
    assert not result["correct"], result["checks"]


def test_serving_correct_and_control_fails(smoke_cell, fake_chip):
    cell, base = smoke_cell("internlm2-1_8b", "serve_offline", SMOKE_LIMITS)
    result, loop = _run(cell, base, fake_chip, seconds=2.0)
    assert result["correct"], result["checks"]
    assert loop.compare(quant="fp8")["served_logit_gap"] > \
        SMOKE_LIMITS["served_logit_gap"]


def _state_unchanged(model_cls, monkeypatch):
    step = model_cls.decode_step

    def broken(self, params, token, pos, cache, sharder):
        logits, _ = step(self, params, token, pos, cache, sharder)
        return logits, cache
    monkeypatch.setattr(model_cls, "decode_step", broken)


def _half_slots(model_cls, monkeypatch):
    step = model_cls.decode_step

    def broken(self, params, token, pos, cache, sharder):
        logits, new = step(self, params, token, pos, cache, sharder)
        half = token.shape[0] // 2
        logits = jnp.concatenate([logits[:half], logits[:half]], axis=0)
        new = jax.tree.map(
            lambda n, o: jnp.concatenate([n[:, :half], o[:, half:]], axis=1),
            new, cache)
        return logits, new
    monkeypatch.setattr(model_cls, "decode_step", broken)


def _token_altered(model_cls, monkeypatch):
    import repro.serving.engine as engine

    def runner_up(logits):
        top2 = jax.lax.top_k(logits, 2)[1]
        return top2[..., 1].astype(jnp.int32)
    monkeypatch.setattr(engine, "greedy", runner_up)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_slots,
                                   _token_altered])
def test_serving_fault_is_not_correct(fault, smoke_cell, fake_chip,
                                      monkeypatch):
    from repro.models import Model

    fault(Model, monkeypatch)
    cell, base = smoke_cell("internlm2-1_8b", "serve_offline", SMOKE_LIMITS)
    # Compare every finished request, so the fault shows whichever slots
    # the compared requests sat in.
    cell.traffic["check_requests"] = 1000
    result, _ = _run(cell, base, fake_chip, seconds=2.0)
    assert not result["correct"], result["checks"]
