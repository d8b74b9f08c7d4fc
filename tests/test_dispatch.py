"""The one path from a shape to a launch config: override > driver > default.

Every tuned op of ``kernels/ops.py`` asks ``choose_or_default`` for its
config inside a ``dispatch.choose`` span and launches its Pallas kernel at
the resolved tiles.  Each test below runs over all nine op cases; the
Pallas functions are replaced by recorders that keep the tiles they are
called with, and ops are only traced (``jax.eval_shape``), so no kernel
executes.  Three model cases check that ``transformer.forward`` resolves
its kernels through the same path at tiny widths.
"""

import functools
import itertools
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import (DriverCache, DriverProgram, Klaraptor, V5E,
                        V5eSimulator, flash_attention_spec, matmul_spec,
                        moe_gmm_spec, registry, set_choice_listener,
                        ssd_scan_spec)
from repro.core import driver as driver_mod
from repro.core.driver import fit_tile
from repro.distributed.sharding import Sharder
from repro.kernels import layernorm as layernorm_mod
from repro.kernels import ops
from repro.kernels import reduce as reduce_mod
from repro.kernels.flash_attention import flash_block_counts
from repro.models import Model, init_params
from repro.models import transformer as T
from repro.trace import Tracer

BF, F32 = jnp.bfloat16, jnp.float32
HEAD_DIM, SSD_HEAD, SSD_STATE = 32, 32, 32   # the smoke models' widths
LN_WIDTH = 256


def _s(*shape, dtype=BF):
    return jax.ShapeDtypeStruct(shape, dtype)


@dataclass(frozen=True)
class Case:
    """One op at one tiny shape: how to call it, what it asks, and which
    tiles a config becomes once fitted to the shape."""

    kernel: str
    spec: Callable[[], object]
    op: Callable
    args: Callable[[int], tuple]   # scale -> argument structs
    dims: Callable[[int], dict]    # scale -> the data parameters D
    default: dict
    fit: Callable[[dict, dict], dict]
    donor: str                     # a case whose driver takes other D


def _fit_matmul(cfg, D):
    return {"bm": fit_tile(D["m"], cfg["bm"], 8),
            "bn": fit_tile(D["n"], cfg["bn"], 128),
            "bk": fit_tile(D["k"], cfg["bk"], 128)}


def _fit_flash(cfg, D):
    return {"bq": fit_tile(D["sq"], cfg["bq"], 8),
            "bkv": fit_tile(D["skv"], cfg["bkv"], 128)}


def _fit_gmm(cfg, D):
    return {"bg": fit_tile(D["g"], cfg["bg"], 8),
            "bn": fit_tile(D["n"], cfg["bn"], 128),
            "bk": fit_tile(D["k"], cfg["bk"], 128)}


def _fit_ssd(cfg, D):
    return {"chunk": fit_tile(D["s"], cfg["chunk"], 128)}


def _matmul_case(dtype, bits):
    return Case(
        kernel=f"matmul_b{bits}", spec=lambda: matmul_spec(bits // 8),
        op=functools.partial(ops.matmul, use_pallas=True),
        args=lambda k: (_s(256 * k, 512, dtype=dtype),
                        _s(512, 1024, dtype=dtype)),
        dims=lambda k: {"m": 256 * k, "n": 1024, "k": 512},
        default=ops.MATMUL_DEFAULT, fit=_fit_matmul, donor="flash_causal")


def _flash_case(causal, window=None):
    return Case(
        kernel=f"flash_attn_d{HEAD_DIM}" + ("_causal" if causal else ""),
        spec=lambda: flash_attention_spec(head_dim=HEAD_DIM, causal=causal),
        op=functools.partial(ops.flash_attention, num_q_heads=2,
                             num_kv_heads=1, causal=causal, window=window,
                             use_pallas=True),
        args=lambda k: (_s(4, 512 * k, HEAD_DIM), _s(2, 512 * k, HEAD_DIM),
                        _s(2, 512 * k, HEAD_DIM)),
        dims=lambda k: {"bh": 4, "sq": 512 * k, "skv": 512 * k},
        default=ops.FLASH_DEFAULT, fit=_fit_flash, donor="matmul_bf16")


def _auto_fit(ak):
    return lambda cfg, D: ak.fit_config(cfg, D)


LN = ops._layernorm_auto(LN_WIDTH, 2)
COLSUM = ops._colsum_auto(2)

CASES = {
    "matmul_bf16": _matmul_case(BF, 16),
    "matmul_f32": _matmul_case(F32, 32),
    "flash_causal": _flash_case(True),
    "flash_noncausal": _flash_case(False),
    "flash_windowed": _flash_case(True, window=128),
    "moe_gmm": Case(
        kernel="moe_gmm_b16", spec=moe_gmm_spec,
        op=functools.partial(ops.moe_gmm, use_pallas=True),
        args=lambda k: (_s(2, 256 * k, 512), _s(2, 512, 512)),
        dims=lambda k: {"e": 2, "g": 256 * k, "k": 512, "n": 512},
        default=ops.GMM_DEFAULT, fit=_fit_gmm, donor="ssd_scan"),
    "ssd_scan": Case(
        kernel=f"ssd_scan_h{SSD_HEAD}_n{SSD_STATE}",
        spec=lambda: ssd_scan_spec(SSD_HEAD, SSD_STATE),
        op=functools.partial(ops.ssd_scan, use_pallas=True),
        args=lambda k: (_s(2, 512 * k, SSD_HEAD), _s(2, 512 * k, dtype=F32),
                        _s(2, 512 * k, SSD_STATE), _s(2, 512 * k, SSD_STATE),
                        _s(2, dtype=F32)),
        dims=lambda k: {"bh": 2, "s": 512 * k, "chunkflops": 1},
        default=ops.SSD_DEFAULT, fit=_fit_ssd, donor="moe_gmm"),
    "layernorm": Case(
        kernel=LN.name, spec=lambda: LN.spec,
        op=functools.partial(ops.layernorm, use_pallas=True),
        args=lambda k: (_s(256 * k, LN_WIDTH), _s(256 * k, LN_WIDTH),
                        _s(LN_WIDTH, dtype=F32), _s(LN_WIDTH, dtype=F32)),
        dims=lambda k: {"r": 256 * k},
        default=LN.defaults, fit=_auto_fit(LN), donor="colsum"),
    "colsum": Case(
        kernel=COLSUM.name, spec=lambda: COLSUM.spec,
        op=functools.partial(ops.blocked_colsum, use_pallas=True),
        args=lambda k: (_s(512 * k, 1024),),
        dims=lambda k: {"r": 512 * k, "c": 1024},
        default=COLSUM.defaults, fit=_auto_fit(COLSUM), donor="layernorm"),
}
OPS = sorted(CASES)
FLASH = {"flash_causal", "flash_noncausal", "flash_windowed"}


@pytest.fixture(scope="module")
def drivers(tmp_path_factory):
    """One driver per kernel, built once and written to one artifact
    cache (registry untouched); the model cases' kernels are among them."""
    root = str(tmp_path_factory.mktemp("dispatch-cache"))
    kl = Klaraptor(V5eSimulator(noise=0.03, seed=7), cache=DriverCache(root))
    built = {}
    for case in CASES.values():
        if case.kernel not in built:
            built[case.kernel] = kl.build_driver(
                case.spec(), repeats=2, max_configs_per_size=8,
                register=False).driver
    return {"root": root, **built}


@pytest.fixture(autouse=True)
def clean(tmp_path, monkeypatch):
    """An empty artifact cache, an empty registry, no listener."""
    monkeypatch.setenv("KLARAPTOR_CACHE_DIR", str(tmp_path / "cache"))
    registry.clear()
    set_choice_listener(None)
    yield
    set_choice_listener(None)
    registry.clear()


@pytest.fixture()
def launches(monkeypatch):
    """Replace each Pallas function by a recorder of its tiles."""
    seen = []

    def recorder(tiles, out):
        def fn(*args, **kw):
            seen.append({t: kw[t] for t in tiles})
            return out(*args, **kw)
        return fn

    def zeros_like_first(x, *a, **kw):
        return jnp.zeros(x.shape, x.dtype)

    patches = [
        (ops, "matmul_pallas", ("bm", "bn", "bk"),
         lambda a, b, out_dtype=None, **kw: jnp.zeros(
             (a.shape[0], b.shape[1]), out_dtype or a.dtype)),
        (ops, "flash_attention_pallas", ("bq", "bkv"), zeros_like_first),
        (ops, "moe_gmm_pallas", ("bg", "bn", "bk"),
         lambda x, w, **kw: jnp.zeros(x.shape[:2] + w.shape[2:], x.dtype)),
        (ops, "ssd_scan_pallas", ("chunk",), zeros_like_first),
        (layernorm_mod, "layernorm_pallas", ("br",), zeros_like_first),
        (reduce_mod, "colsum_pallas", ("br", "bc"),
         lambda x, **kw: jnp.zeros((8, x.shape[1]), F32)),
    ]
    for mod, name, tiles, out in patches:
        monkeypatch.setattr(mod, name, recorder(tiles, out))
    return seen


@pytest.fixture()
def events():
    seen = []
    set_choice_listener(seen.append)
    return seen


def _trace(case, scale=1):
    """Trace the op once, through a fresh function (no trace cache)."""
    jax.eval_shape(lambda *a: case.op(*a), *case.args(scale))


def _override(case, drv):
    """A candidate config whose fitted tiles differ from the driver's."""
    D = case.dims(1)
    chosen = case.fit(drv.choose(D), D)
    cands = case.spec().param_candidates
    names = sorted(cands)
    for values in itertools.product(*(cands[n] for n in names)):
        cfg = dict(zip(names, values))
        if case.fit(cfg, D) != chosen:
            return cfg
    raise AssertionError(f"no candidate of {case.kernel} differs")


@pytest.mark.parametrize("name", OPS)
def test_no_driver_launches_the_fitted_default(name, launches, events):
    case = CASES[name]
    _trace(case)
    assert launches == [case.fit(case.default, case.dims(1))]
    assert [(e.kernel, e.D) for e in events] == [(case.kernel,
                                                 case.dims(1))]


@pytest.mark.parametrize("name", OPS)
def test_registered_driver_chooses_the_tiles(name, drivers, launches):
    case = CASES[name]
    drv = drivers[case.kernel]
    registry.register(drv)
    _trace(case)
    D = case.dims(1)
    assert launches == [case.fit(drv.choose(D), D)]


@pytest.mark.parametrize("name", OPS)
def test_pinned_override_outranks_the_driver(name, drivers, launches):
    case = CASES[name]
    drv = drivers[case.kernel]
    registry.register(drv)
    cfg = _override(case, drv)
    registry.note_override(case.kernel, V5E.name, case.dims(1), cfg)
    _trace(case)
    assert launches == [case.fit(cfg, case.dims(1))]


@pytest.mark.parametrize("name", OPS)
def test_mismatched_driver_falls_back_to_default(name, drivers, launches,
                                                 events):
    case = CASES[name]
    donor = drivers[CASES[case.donor].kernel]
    assert set(donor.data_params) != set(case.dims(1))
    registry.register(DriverProgram.from_source(case.kernel, donor.source))
    _trace(case)
    assert launches == [case.fit(case.default, case.dims(1))]
    assert [e.source for e in events] == ["default"]


@pytest.mark.parametrize("name", OPS)
def test_one_decision_per_shape_and_trace_under_jit(name, drivers, events):
    case = CASES[name]
    registry.register(drivers[case.kernel])
    step = jax.jit(lambda *a: case.op(*a))
    for scale in (1, 1, 1, 2, 2):
        jax.eval_shape(step, *case.args(scale))
    assert [e.D for e in events] == [case.dims(1), case.dims(2)]
    assert {e.source for e in events} == {"driver"}


@pytest.mark.parametrize("name", OPS)
def test_no_stale_decision_survives_a_registry_change(name, drivers,
                                                      launches):
    case = CASES[name]
    D = case.dims(1)
    drv = drivers[case.kernel]
    _trace(case)
    registry.register(drv)
    _trace(case)
    cfg = _override(case, drv)
    registry.note_override(case.kernel, V5E.name, D, cfg)
    _trace(case)
    assert launches == [case.fit(case.default, D),
                        case.fit(drv.choose(D), D), case.fit(cfg, D)]


@pytest.mark.parametrize("name", OPS)
def test_driver_read_back_from_the_cache_chooses_alike(name, drivers,
                                                       launches, events,
                                                       monkeypatch):
    case = CASES[name]
    monkeypatch.setenv("KLARAPTOR_CACHE_DIR", drivers["root"])
    fresh = driver_mod._Registry()
    monkeypatch.setattr(driver_mod, "registry", fresh)
    _trace(case)
    D = case.dims(1)
    loaded = fresh.get(case.kernel)
    assert loaded is not None and loaded is not drivers[case.kernel]
    assert launches == [case.fit(drivers[case.kernel].choose(D), D)]
    assert [e.source for e in events] == ["driver"]


@pytest.mark.parametrize("name", OPS)
def test_dispatch_span_names_the_kernel(name, drivers, launches):
    case = CASES[name]
    registry.register(drivers[case.kernel])
    with Tracer() as tracer:
        _trace(case)
    spans = tracer.spans()
    assert [s.name for s in spans] == ["dispatch.choose"]
    attrs = spans[0].attrs
    if name in FLASH:
        D = case.dims(1)
        tiles = launches[0]
        assert attrs == {"kernel": case.kernel, **flash_block_counts(
            D["bh"], D["sq"], D["skv"], tiles["bq"], tiles["bkv"],
            case.op.keywords["causal"], case.op.keywords["window"])}
    else:
        assert attrs == {"kernel": case.kernel}


@pytest.mark.parametrize("name", OPS)
def test_listener_source_follows_the_ladder(name, drivers, events):
    case = CASES[name]
    drv = drivers[case.kernel]
    _trace(case)
    registry.register(drv)
    _trace(case)
    registry.note_override(case.kernel, V5E.name, case.dims(1),
                           _override(case, drv))
    _trace(case)
    assert [e.source for e in events] == ["default", "driver", "override"]
    assert events[0].predicted_s is None
    assert events[1].predicted_s == pytest.approx(
        drv.estimate(case.dims(1), events[1].config))


@pytest.mark.parametrize("name", OPS)
def test_reference_path_makes_no_decision(name, launches, events):
    case = CASES[name]
    ref_op = functools.partial(case.op.func, **{**case.op.keywords,
                                                "use_pallas": False})
    jax.eval_shape(lambda *a: ref_op(*a), *case.args(1))
    assert launches == [] and events == []


# ---------------------------------------------------------------------------
# transformer.forward: each benchmark family at its smoke widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-130m",
                                  "granite-4.0-h-small"])
def test_forward_resolves_its_kernels_through_the_driver(arch, drivers,
                                                         events):
    cfg = get_config(arch, smoke=True).replace(use_pallas=True)
    for case in ("flash_causal", "ssd_scan"):
        registry.register(drivers[CASES[case].kernel])
    params = jax.eval_shape(
        lambda: init_params(Model(cfg).specs(), jax.random.PRNGKey(0)))
    x = _s(2, 256, cfg.d_model, dtype=cfg.dtype)
    jax.eval_shape(lambda p, x: T.forward(cfg, p, x, Sharder(mesh=None)),
                   params, x)
    kinds = [d.kind for d in cfg.block_pattern]
    want = {CASES["flash_causal"].kernel: kinds.count("attn"),
            CASES["ssd_scan"].kernel: kinds.count("mamba")}
    got = {k: sum(e.kernel == k for e in events) for k in want}
    assert got == want
    assert {e.source for e in events} == {"driver"}
    for e in events:     # one config per kernel and shape
        assert e.config == drivers[e.kernel].choose(e.D)
