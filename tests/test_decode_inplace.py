"""The decode step's in-place cache write against the one-hot write it
replaced.

``transformer.decode_step`` carries the stacked cache through the layer
scan and stores each token's k/v row by a scatter.  The reference below
is the former step: a loop over layer groups that rebuilds each group's
cache, writing a token by a one-hot multiply-add over the layer's whole
cache.  Both run several steps with the slots at distinct positions, up
to ``max_seq - 1`` and past it; every cache leaf must match to the bit,
and a position outside ``[0, max_seq)`` must write nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import Sharder
from repro.models import Model
from repro.models import layers as L
from repro.models import transformer as T

SH = Sharder(mesh=None)
ARCHS = ["internlm2-1.8b", "gemma2-2b", "granite-4.0-h-small"]
B, S, STEPS = 4, 24, 5
# First positions of the four slots.  The last walks from S - 3 to S + 1,
# so it writes S - 1 and then twice past the end; the last two reach past
# gemma2's smoke window of 16.
START = np.array([0, 5, 17, S - 3], np.int32)


def _onehot_write(cache, new, pos, layer):
    """The former write: a one-hot multiply-add over the layer's cache."""
    onehot = jax.nn.one_hot(pos, cache.shape[2], dtype=cache.dtype)
    onehot = onehot[:, :, None, None]
    written = cache[layer] * (1.0 - onehot) + onehot * new.astype(cache.dtype)
    return cache.at[layer].set(written)


def _reference_step(cfg, params, token, pos, cache):
    """The former decode step: the cache scanned over as the layer
    scan's input, each group's slice updated by the one-hot write and
    stacked again as its output."""
    x = T.embed_tokens(cfg, params, token[:, None])

    def group_body(x, scanned):
        gp, gc = scanned
        new = {}
        for i, desc in enumerate(cfg.block_pattern):
            p, c, nc = gp[f"pos{i}"], gc[f"pos{i}"], {}
            h = L.rmsnorm(x, p["norm"], cfg.rms_eps)
            if desc.kind == "attn":
                y, k, v = L.attention_decode(cfg, p, h, SH, desc, pos,
                                             c["k"][None], c["v"][None], 0)
                nc["k"], nc["v"] = k[0], v[0]
            else:
                y, nc["conv"], nc["ssm"] = L.mamba_decode(
                    cfg, p, h, c["conv"], c["ssm"])
            x = T._residual(cfg, x, y)
            if desc.mlp:
                y, _ = T._feed_forward(cfg, desc, p, x, SH)
                x = T._residual(cfg, x, y)
            new[f"pos{i}"] = nc
        return x, new

    x, cache = jax.lax.scan(group_body, x, (params["blocks"], cache))
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return T.unembed(cfg, params, x[:, 0]), cache


def _random_cache(model, key):
    """A cache full of values, so that a row left alone is told apart
    from one written with zeros."""
    specs = jax.eval_shape(lambda: model.init_cache(B, S))
    leaves, tree = jax.tree.flatten(specs)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


def _bits(a):
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


@pytest.fixture(scope="module", params=ARCHS)
def setup(request):
    cfg = get_config(request.param, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = _random_cache(model, jax.random.PRNGKey(1))
    step = jax.jit(lambda p, t, pos, c: model.decode_step(p, t, pos, c, SH))
    tokens = np.random.RandomState(2).randint(
        2, cfg.vocab_size, (STEPS, B)).astype(np.int32)
    refs, c = [], cache
    with pytest.MonkeyPatch.context() as mp:
        # The reference is traced, on its first call, with the former write.
        mp.setattr(L, "_write_cache", _onehot_write)
        ref = jax.jit(lambda p, t, pos, c: _reference_step(cfg, p, t, pos, c))
        for t in range(STEPS):
            logits, c = ref(params, jnp.asarray(tokens[t]),
                            jnp.asarray(START + t), c)
            refs.append((np.asarray(logits), jax.tree.map(np.asarray, c)))
    return cfg, params, cache, step, tokens, refs


def test_steps_match_the_onehot_write(setup):
    cfg, params, cache, step, tokens, refs = setup
    c = cache
    for t in range(STEPS):
        logits, c = step(params, jnp.asarray(tokens[t]),
                         jnp.asarray(START + t), c)
        want_logits, want_cache = refs[t]
        got = np.asarray(logits)[:, :cfg.vocab_size]
        want = want_logits[:, :cfg.vocab_size]
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(c),
                                jax.tree.leaves(want_cache)):
            np.testing.assert_array_equal(
                _bits(g), _bits(w),
                err_msg=f"step {t} {jax.tree_util.keystr(path)}")


@pytest.mark.parametrize("outside", [S, S + 1, -1])
def test_a_position_outside_the_cache_writes_nothing(setup, outside):
    cfg, params, cache, step, tokens, _ = setup
    pos = START.copy()
    pos[1] = outside
    _, new = step(params, jnp.asarray(tokens[0]), jnp.asarray(pos), cache)
    for i, desc in enumerate(cfg.block_pattern):
        if desc.kind != "attn":
            continue
        for name in ("k", "v"):
            old_kv = np.asarray(cache[f"pos{i}"][name])
            new_kv = np.asarray(new[f"pos{i}"][name])
            np.testing.assert_array_equal(_bits(new_kv[:, 1]),
                                          _bits(old_kv[:, 1]))
            # The other slots wrote their row and nothing else.
            for b in (0, 2, 3):
                changed = np.nonzero(
                    (_bits(new_kv[:, b]) != _bits(old_kv[:, b])).any(
                        axis=(0, 2, 3)))[0]
                assert set(changed) <= {pos[b]}, (name, b, changed)
