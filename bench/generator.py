"""The one traffic generator: turns a traffic mix (``bench/traffic/*.json``)
and a seed into inputs.

Every seed gets the same work.  Lengths are a fixed set of quantiles of
the mix's distributions; the seed draws only their order and the token
ids.  So two seeds differ in which request comes when, not in how much
there is to do, and their runs can be compared with each other.

Kinds of mix:

* ``forward``: rows of ``seq_len`` token ids, ``batch`` rows per step,
  ``pool`` distinct batches used in turn (a closed loop).
* ``serve_offline``: a backlog of ``backlog`` requests, all due at once,
  with lognormal prompt and output lengths.

A serving mix with ``"fixed_order": true`` gives every seed the same
lengths in the same order, and only its own token ids: for a window
that sees a few of many long requests, where the order would decide how
much work falls into it.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

__all__ = ["Planned", "lognormal_set", "forward_tokens",
           "requests", "rng_for"]

FIRST_TOKEN_ID = 2   # ids 0 and 1 are padding and end of sequence


@dataclass
class Planned:
    """One request as the generator plans it."""
    rid: int
    prompt: list[int]
    max_new_tokens: int


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose, any non-negative seed."""
    return np.random.default_rng([seed, sum(map(ord, stream)) * 7919])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of a lognormal with the
    given ``median`` and ``sigma`` (of the log), clipped to [min, max]."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def forward_tokens(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """(pool, batch, seq_len) int32 token ids."""
    rng = rng_for(seed, "forward")
    shape = (mix["pool"], mix["batch"], mix["seq_len"])
    return rng.integers(FIRST_TOKEN_ID, vocab, shape, dtype=np.int32)


def requests(mix: dict, seed: int, vocab: int, n: int) -> list[Planned]:
    """``n`` requests of a serving mix, in the order they are queued."""
    if mix["kind"] != "serve_offline":
        raise ValueError(f"not a serving mix: {mix['kind']!r}")
    order = rng_for(0 if mix.get("fixed_order") else seed, "order")
    prompts = order.permutation(lognormal_set(mix["prompt"], n))
    outputs = order.permutation(lognormal_set(mix["output"], n))
    ids = rng_for(seed, "tokens")
    return [Planned(rid=i,
                    prompt=ids.integers(FIRST_TOKEN_ID, vocab,
                                        int(prompts[i])).tolist(),
                    max_new_tokens=int(outputs[i]))
            for i in range(n)]
