"""Zero-host-overhead dispatch: device plan tables, per-step launch plans,
and the choose_or_default decision memo.

Load-bearing properties:
  * ``DevicePlanTable`` lookups are bit-identical to the host
    ``LaunchPlanTable`` on every tier-1 kernel -- hits, misses, and hash
    collisions (the 32-bit device hash collides more readily than the
    64-bit host hash; dims verification must make that invisible).
  * A frozen ``StepPlan`` never serves across a registry generation bump
    (refit hot-swap, pinned override, new plan) -- and the fall-through
    ordering makes "pinned override > step plan > registry" hold.
  * The decision memo serves bit-identical repeats, dies with the
    generation, and keeps telemetry honest (full-fidelity window, then
    coalesced events whose n_coalesced preserves launch counts).
"""

import numpy as np
import pytest

from repro.core import (DriverProgram, Klaraptor, V5E, V5eSimulator,
                        choose_or_default, compile_plan, dkey,
                        flash_attention_spec, lattice, matmul_spec,
                        memo_key, moe_gmm_spec, registry,
                        set_choice_listener, set_decision_memo,
                        ssd_scan_spec)
from repro.core.device_plan import DevicePlanTable, pack_shape32
from repro.core.plan import LaunchPlanTable
from repro.core.step_plan import (KernelRequest, StepPlan, active_step_plan,
                                  build_step_plan, use_step_plan)

SPECS = {
    "matmul": matmul_spec,
    "flash": flash_attention_spec,
    "moe": moe_gmm_spec,
    "ssd": ssd_scan_spec,
}

ENVELOPES = {
    "matmul": {"m": [512, 1024, 2048, 4096], "n": [512, 1024, 2048, 4096],
               "k": [512, 1024]},
    "flash": {"bh": [2, 8], "sq": [512, 1024, 2048, 4096],
              "skv": [1024, 2048]},
    "moe": {"e": [2, 8], "g": [256, 1024], "k": [512, 1024],
            "n": [512, 1024]},
    "ssd": {"bh": [2, 8], "s": [1024, 2048, 4096], "chunkflops": [1]},
}


@pytest.fixture(scope="module")
def builds():
    """One driver per tier-1 spec, built once (registry untouched)."""
    sim = V5eSimulator(noise=0.03, seed=7)
    kl = Klaraptor(sim, cache=False)
    return {name: kl.build_driver(fn(), repeats=2, max_configs_per_size=16,
                                  register=False)
            for name, fn in SPECS.items()}


@pytest.fixture()
def clean(tmp_path, monkeypatch):
    monkeypatch.setenv("KLARAPTOR_CACHE_DIR", str(tmp_path / "cache"))
    registry.clear()
    set_choice_listener(None)
    yield
    registry.clear()
    set_choice_listener(None)


def _rows(driver, cols):
    n = next(iter(cols.values())).shape[0]
    return [{d: int(cols[d][i]) for d in driver.data_params}
            for i in range(n)]


# ---------------------------------------------------------------------------
# DevicePlanTable: bit-identity with the host table on all tier-1 kernels
# ---------------------------------------------------------------------------

class TestDevicePlanTable:
    @pytest.mark.parametrize("name", sorted(SPECS))
    def test_bit_identical_hits_and_misses(self, builds, name):
        driver = builds[name].driver
        cols = lattice(ENVELOPES[name])
        table = compile_plan(driver, cols)
        dev = table.to_device()
        assert len(dev) == len(table)
        # every envelope point: identical config dict (hit for hit)
        for D in _rows(driver, cols):
            assert dev.lookup_dims(D) == table.lookup(D), (name, D)
        # misses: perturbed shapes, missing data params, extra keys ignored
        some = _rows(driver, cols)[0]
        off = {d: v + 1 for d, v in some.items()}
        assert dev.lookup_dims(off) == table.lookup(off)
        partial = dict(list(some.items())[:-1])
        assert dev.lookup_dims(partial) is None \
            and table.lookup(partial) is None
        extra = {**some, "zzz": 1}
        assert dev.lookup_dims(extra) == table.lookup(extra)

    def test_in_graph_lookup(self, builds):
        """The probe is jit-traceable: callable from inside a compiled step
        with array inputs, matching the host lookup's row."""
        import jax
        import jax.numpy as jnp

        driver = builds["matmul"].driver
        table = compile_plan(driver, lattice(ENVELOPES["matmul"]))
        dev = table.to_device()

        @jax.jit
        def step(keys):
            row, found = dev.lookup(keys)
            return row, found

        D = {"m": 1024, "n": 2048, "k": 512}
        row, found = step(jnp.array([1024, 2048, 512], dtype=jnp.int32))
        want = table.lookup(D)
        assert bool(found)
        assert {p: int(np.asarray(row)[i])
                for i, p in enumerate(dev.program_params)} == want
        _, found = step(jnp.array([999, 2048, 512], dtype=jnp.int32))
        assert not bool(found)

    def test_vmapped_batched_lookup(self, builds):
        """``lookup`` composes with ``jax.vmap``: one batched probe over a
        mixed batch of hits and misses resolves row-per-key, bit-identical
        to the host table -- the shape a batched serving step would use."""
        import jax
        import jax.numpy as jnp

        driver = builds["matmul"].driver
        cols = lattice(ENVELOPES["matmul"])
        table = compile_plan(driver, cols)
        dev = table.to_device()

        batch_D = _rows(driver, cols)[:6] + [
            {"m": 999, "n": 2048, "k": 512},      # miss: unplanned shape
            {"m": 0, "n": 0, "k": 0},             # miss: degenerate key
        ]
        keys = jnp.asarray([[D[d] for d in driver.data_params]
                            for D in batch_D], dtype=jnp.int32)
        batched = jax.jit(jax.vmap(dev.lookup))
        rows, found = batched(keys)
        assert rows.shape == (len(batch_D), len(dev.program_params))
        assert found.shape == (len(batch_D),)
        for i, D in enumerate(batch_D):
            want = table.lookup(D)
            if want is None:
                assert not bool(found[i]), D
            else:
                assert bool(found[i]), D
                assert {p: int(np.asarray(rows)[i][j])
                        for j, p in enumerate(dev.program_params)} == want

    def test_in_jit_miss_takes_default_branch(self, builds):
        """A lookup miss inside a compiled step selects the fallback
        branch (no retrace, no host round-trip): the step stays at one
        trace across hits and misses."""
        import jax
        import jax.numpy as jnp

        driver = builds["matmul"].driver
        table = compile_plan(driver, lattice(ENVELOPES["matmul"]))
        dev = table.to_device()
        traces = {"n": 0}
        sentinel = jnp.full((len(dev.program_params),), -7, jnp.int32)

        @jax.jit
        def step(keys):
            traces["n"] += 1
            row, found = dev.lookup(keys)
            return jnp.where(found, row, sentinel), found

        hit_D = {"m": 1024, "n": 2048, "k": 512}
        row, found = step(jnp.asarray([1024, 2048, 512], jnp.int32))
        assert bool(found)
        assert {p: int(np.asarray(row)[i])
                for i, p in enumerate(dev.program_params)} == \
            table.lookup(hit_D)
        row, found = step(jnp.asarray([1024, 2048, 513], jnp.int32))
        assert not bool(found)
        assert np.asarray(row).tolist() == [-7] * len(dev.program_params)
        assert traces["n"] == 1

    def test_slot_collisions_resolved(self):
        """Keys whose home slots collide (forced linear-probe chain) all
        resolve to their own configs, on host and device."""
        # find 6 single-dim keys sharing one home slot at capacity 16
        cap, target, keys = 16, None, []
        v = 1
        while len(keys) < 6:
            slot = pack_shape32((v,)) & (cap - 1)
            if target is None:
                target = slot
            if slot == target:
                keys.append(v)
            v += 1
        # capacity for 6 entries is 16, so all six chain off one slot
        table = LaunchPlanTable.build(
            "k", V5E.name, ("a",), ("x",),
            {"a": np.array(keys)}, {"x": np.array([10 * k for k in keys])})
        dev = table.to_device()
        assert dev.capacity == cap and dev.max_probe >= len(keys)
        for k in keys:
            assert dev.lookup_dims({"a": k}) == {"x": 10 * k}
            assert dev.lookup_dims({"a": k}) == table.lookup({"a": k})
        # a probe that walks the full chain and still misses
        miss = next(v for v in range(v, v + 10 ** 6)
                    if (pack_shape32((v,)) & (cap - 1)) == target
                    and v not in keys)
        assert dev.lookup_dims({"a": miss}) is None

    def test_full_hash_collision_is_safe(self):
        """Two distinct shapes with the same 32-bit packed hash must never
        serve each other's config: dims are verified on every probe.

        Single-element keys can't collide (the fmix32 chain is bijective in
        one value), so the birthday search runs over two-dim shapes.
        """
        seen: dict[int, tuple[int, int]] = {}
        a = b = None
        for v in range(1, 1 << 22):
            key = (v & 0xFFFF, v >> 16)
            h = pack_shape32(key)
            if h in seen and seen[h] != key:
                a, b = seen[h], key
                break
            seen[h] = key
        assert a is not None, "no 32-bit collision found in range"
        assert a != b and pack_shape32(a) == pack_shape32(b)
        table = LaunchPlanTable.build(
            "k", V5E.name, ("p", "q"), ("x",),
            {"p": np.array([a[0]]), "q": np.array([a[1]])},
            {"x": np.array([111])})
        dev = table.to_device()
        assert dev.lookup_dims({"p": a[0], "q": a[1]}) == {"x": 111}
        # hash hit, dims differ: the probe must reject, not serve a's config
        assert dev.lookup_dims({"p": b[0], "q": b[1]}) is None
        # and with both inserted, each gets exactly its own config
        table2 = LaunchPlanTable.build(
            "k", V5E.name, ("p", "q"), ("x",),
            {"p": np.array([a[0], b[0]]), "q": np.array([a[1], b[1]])},
            {"x": np.array([111, 222])})
        dev2 = table2.to_device()
        assert dev2.lookup_dims({"p": a[0], "q": a[1]}) == {"x": 111}
        assert dev2.lookup_dims({"p": b[0], "q": b[1]}) == {"x": 222}

    def test_empty_table(self):
        table = LaunchPlanTable.build("k", V5E.name, ("a",), ("x",),
                                      {"a": np.array([], dtype=np.int64)},
                                      {"x": np.array([], dtype=np.int64)})
        dev = table.to_device()
        assert len(dev) == 0
        assert dev.lookup_dims({"a": 7}) is None


# ---------------------------------------------------------------------------
# StepPlan: batched build, bit-identity, generation invalidation
# ---------------------------------------------------------------------------

class TestStepPlan:
    def _requests(self, driver, cols, default=None):
        return [KernelRequest.make(driver.kernel, D,
                                   default or {"zz": -1})
                for D in _rows(driver, cols)]

    def test_build_matches_choose_bit_identical(self, clean, builds):
        """StepPlan's batched sweep must pick what per-shape choose()
        picks, for every tier-1 kernel in one multi-kernel build."""
        from repro.core import register_driver
        reqs = []
        for name in sorted(SPECS):
            register_driver(builds[name].driver)
        for name in sorted(SPECS):
            driver = builds[name].driver
            reqs += self._requests(driver, lattice(ENVELOPES[name]))
        plan = build_step_plan(reqs)
        assert plan.describe()["sources"] == {"driver": len(plan)}
        for name in sorted(SPECS):
            driver = builds[name].driver
            for D in _rows(driver, lattice(ENVELOPES[name])):
                driver.namespace["_HISTORY"].clear()
                assert plan.resolve(driver.kernel, D) == driver.choose(D), \
                    (name, D)

    def test_default_for_untuned_kernel(self, clean):
        plan = build_step_plan([KernelRequest.make(
            "nonexistent", {"m": 8}, {"bm": 128})])
        assert plan.resolve("nonexistent", {"m": 8}) == {"bm": 128}
        assert plan.describe()["sources"] == {"default": 1}

    def test_override_and_plan_outrank_driver_at_build(self, clean, builds):
        from repro.core import register_driver
        driver = builds["matmul"].driver
        register_driver(driver)
        registry.register_plan(compile_plan(driver,
                                            lattice(ENVELOPES["matmul"])))
        D_pin = {"m": 512, "n": 512, "k": 512}
        pinned = {"bm": 8, "bn": 128, "bk": 128}
        registry.note_override(driver.kernel, V5E.name, D_pin, pinned)
        D_plan = {"m": 1024, "n": 2048, "k": 512}
        D_out = {"m": 96, "n": 384, "k": 640}       # outside the envelope
        plan = build_step_plan([
            KernelRequest.make(driver.kernel, D, {"bm": -1})
            for D in (D_pin, D_plan, D_out)])
        assert plan.resolve(driver.kernel, D_pin) == pinned
        src = plan.sources
        assert src[(driver.kernel, dkey(D_pin))] == "override"
        assert src[(driver.kernel, dkey(D_plan))] == "plan"
        assert src[(driver.kernel, dkey(D_out))] == "driver"

    def test_generation_bump_invalidates(self, clean, builds):
        from repro.core import register_driver
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        plan = build_step_plan([KernelRequest.make(driver.kernel, D,
                                                   {"bm": -1})])
        assert not plan.stale()
        assert plan.resolve(driver.kernel, D) is not None
        registry.note_override(driver.kernel, V5E.name, D,
                               {"bm": 8, "bn": 128, "bk": 128})
        assert plan.stale()
        assert plan.resolve(driver.kernel, D) is None

    def test_refit_hot_swap_invalidates(self, clean, builds):
        from repro.core import register_driver
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        plan = build_step_plan([KernelRequest.make(driver.kernel, D,
                                                   {"bm": -1})])
        # the hot-swap path: invalidate + register a refit generation
        registry.invalidate_kernel(driver.kernel)
        assert plan.stale() and plan.resolve(driver.kernel, D) is None
        refit = DriverProgram.from_source(
            driver.kernel, driver.source + "\n# refit\n", driver.hw,
            tuning_version=1)
        register_driver(refit)
        assert plan.resolve(driver.kernel, D) is None
        # a rebuilt plan against the new generation serves again
        plan2 = build_step_plan([KernelRequest.make(driver.kernel, D,
                                                    {"bm": -1})])
        assert plan2.resolve(driver.kernel, D) == refit.choose(D)

    def test_mid_build_mutation_births_stale_plan(self, clean, builds):
        """A generation bump between snapshot and freeze must produce a
        plan that refuses to serve (mirrors memo_store's guard)."""
        from repro.core import register_driver
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        gen = registry.generation
        plan = build_step_plan([KernelRequest.make(driver.kernel, D,
                                                   {"bm": -1})])
        assert plan.generation == gen
        # simulate the mutation landing right after the snapshot
        stale = StepPlan(hw_name=plan.hw_name, generation=gen - 1,
                         table=plan.table, sources=plan.sources)
        assert stale.resolve(driver.kernel, D) is None


# ---------------------------------------------------------------------------
# Ops-level dispatch: plan context, precedence, no registry traffic
# ---------------------------------------------------------------------------

class TestOpsDispatch:
    def test_context_and_explicit_plan(self, clean):
        import repro.kernels.ops as ops
        D = {"m": 64, "n": 64, "k": 64}
        plan = build_step_plan([KernelRequest.make(
            "matmul_b32", D, {"bm": 8, "bn": 128, "bk": 128})])
        assert active_step_plan() is None
        with use_step_plan(plan):
            assert active_step_plan() is plan
            got = ops._resolve("matmul_b32", D, ops.MATMUL_DEFAULT, None)
            assert got == {"bm": 8, "bn": 128, "bk": 128}
            with use_step_plan(None):      # inner disable
                assert active_step_plan() is None
        assert active_step_plan() is None
        # explicit argument, no ambient context
        got = ops._resolve("matmul_b32", D, ops.MATMUL_DEFAULT, plan)
        assert got == {"bm": 8, "bn": 128, "bk": 128}

    def test_plan_hit_makes_no_registry_traffic(self, clean):
        import repro.kernels.ops as ops
        D = {"m": 64, "n": 64, "k": 64}
        plan = build_step_plan([KernelRequest.make(
            "matmul_b32", D, {"bm": 8, "bn": 128, "bk": 128})])
        events = []
        set_choice_listener(events.append)
        before = registry.stats()
        with use_step_plan(plan):
            ops._resolve("matmul_b32", D, ops.MATMUL_DEFAULT, None)
        assert events == []                      # no ChoiceEvent emitted
        assert registry.stats() == before        # no counters touched

    def test_pinned_override_outranks_step_plan(self, clean):
        """The acceptance ordering: a fresh override beats a frozen plan
        (the bump stales the plan; choose_or_default serves the pin)."""
        import repro.kernels.ops as ops
        D = {"m": 64, "n": 64, "k": 64}
        plan = build_step_plan([KernelRequest.make(
            "matmul_b32", D, {"bm": 256, "bn": 256, "bk": 256})])
        pinned = {"bm": 8, "bn": 128, "bk": 128}
        registry.note_override("matmul_b32", V5E.name, D, pinned)
        with use_step_plan(plan):
            assert ops._resolve("matmul_b32", D,
                                ops.MATMUL_DEFAULT, None) == pinned

    def test_step_plan_outranks_registry_driver(self, clean, builds):
        from repro.core import register_driver
        import repro.kernels.ops as ops
        driver = builds["matmul"].driver     # kernel name "matmul_b16"
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        marked = {"bm": 8, "bn": 128, "bk": 128}
        plan = StepPlan(hw_name=V5E.name, generation=registry.generation,
                        table={(driver.kernel, dkey(D)): marked},
                        sources={(driver.kernel, dkey(D)): "test"})
        with use_step_plan(plan):
            assert ops._resolve(driver.kernel, D,
                                ops.MATMUL_DEFAULT, None) == marked
        # without the plan, the registered driver decides
        assert ops._resolve(driver.kernel, D,
                            ops.MATMUL_DEFAULT, None) != marked

    def test_pallas_op_runs_under_step_plan(self, clean):
        import jax.numpy as jnp

        import repro.kernels.ops as ops
        D = {"m": 16, "n": 128, "k": 128}
        plan = build_step_plan([KernelRequest.make(
            "matmul_b32", D, {"bm": 8, "bn": 128, "bk": 128})])
        x = jnp.ones((16, 128), jnp.float32)
        y = jnp.ones((128, 128), jnp.float32)
        events = []
        set_choice_listener(events.append)
        with use_step_plan(plan):
            out = ops.matmul(x, y, use_pallas=True)
        assert out.shape == (16, 128)
        np.testing.assert_allclose(np.asarray(out), 128.0)
        assert events == []                 # dispatched from the plan


# ---------------------------------------------------------------------------
# Decision memo: fast-path identity, invalidation, telemetry accounting
# ---------------------------------------------------------------------------

class TestDecisionMemo:
    def test_repeat_is_bit_identical_and_memoized(self, clean, builds):
        from repro.core import register_driver
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        first = choose_or_default(driver.kernel, D, {"bm": -1})
        ent = registry.memo_get(memo_key(driver.kernel, V5E.name, D))
        assert ent is not None and ent[1] == "driver"
        second = choose_or_default(driver.kernel, D, {"bm": -1})
        third = choose_or_default(driver.kernel, D, {"bm": -1})
        assert second == first
        # memo hits share one read-only dict (the entry's private copy,
        # never the slow path's return value)
        assert second is not first and second is third
        assert registry.memo_hits() == 2

    def test_generation_bump_drops_memo(self, clean, builds):
        from repro.core import register_driver
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        choose_or_default(driver.kernel, D, {"bm": -1})
        pinned = {"bm": 8, "bn": 128, "bk": 128}
        registry.note_override(driver.kernel, V5E.name, D, pinned)
        assert registry.memo_get(
            memo_key(driver.kernel, V5E.name, D)) is None
        assert choose_or_default(driver.kernel, D, {"bm": -1}) == pinned

    def test_default_path_not_memoized(self, clean):
        cfg = choose_or_default("untuned_kernel", {"m": 8}, {"bm": 64})
        assert cfg == {"bm": 64}
        assert registry.memo_get(
            memo_key("untuned_kernel", V5E.name, {"m": 8})) is None
        # different call sites may pass different defaults; each must win
        assert choose_or_default("untuned_kernel", {"m": 8},
                                 {"bm": 32}) == {"bm": 32}

    def test_no_estimate_without_listener(self, clean, builds):
        """Satellite: an untelemetered launch must not pay estimate()."""
        from repro.core import register_driver
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        calls = {"n": 0}
        inner = driver.namespace["estimate"]

        def counting(**kw):
            calls["n"] += 1
            return inner(**kw)

        driver.namespace["estimate"] = counting
        try:
            choose_or_default(driver.kernel, D, {"bm": -1})
            baseline = calls["n"]   # choose() itself may estimate
            for _ in range(5):
                choose_or_default(driver.kernel, D, {"bm": -1})
            assert calls["n"] == baseline       # memo hits: zero estimates
            set_choice_listener(lambda e: None)
            choose_or_default(driver.kernel, D, {"bm": -1})
            assert calls["n"] == baseline + 1   # listener: fresh prediction
        finally:
            driver.namespace["estimate"] = inner

    def test_full_window_then_coalesced_events(self, clean, builds):
        from repro.core import register_driver
        from repro.core.driver import MEMO_FULL_WINDOW, MEMO_NOTIFY_EVERY
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        events = []
        set_choice_listener(events.append)
        total = 1 + MEMO_FULL_WINDOW + MEMO_NOTIFY_EVERY
        for _ in range(total):
            choose_or_default(driver.kernel, D, {"bm": -1})
        # slow path + full-fidelity window + exactly one coalesced event
        assert len(events) == 1 + MEMO_FULL_WINDOW + 1
        window = events[:1 + MEMO_FULL_WINDOW]
        assert all(e.n_coalesced == 1 and e.source == "driver"
                   and e.predicted_s is not None for e in window)
        assert events[-1].n_coalesced == MEMO_NOTIFY_EVERY
        # every launch accounted for exactly once
        assert sum(e.n_coalesced for e in events) == total

    def test_telemetry_counts_coalesced_launches(self, clean, builds):
        from repro.core import register_driver
        from repro.core.driver import MEMO_FULL_WINDOW, MEMO_NOTIFY_EVERY
        from repro.telemetry import Telemetry, TelemetryConfig
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        # refits disabled: a drift-triggered refit would bump the registry
        # generation mid-loop and (correctly) drop pending coalesced hits,
        # which is not the accounting identity under test here.
        tel = Telemetry([matmul_spec()], V5eSimulator(seed=0), cache=False,
                        config=TelemetryConfig(refit_enabled=False))
        total = 1 + MEMO_FULL_WINDOW + MEMO_NOTIFY_EVERY
        with tel:
            for _ in range(total):
                choose_or_default(driver.kernel, D, {"bm": -1})
        snap = tel.snapshot()
        assert snap["counters"]["choices_total"] == total
        assert snap["counters"]["choices_by_source"] == {"driver": total}
        (key,) = snap["keys"]
        assert key["n_choices"] == total

    def test_disable_enable(self, clean, builds):
        from repro.core import register_driver
        driver = builds["matmul"].driver
        register_driver(driver)
        D = {"m": 1024, "n": 2048, "k": 512}
        prev = set_decision_memo(False)
        try:
            choose_or_default(driver.kernel, D, {"bm": -1})
            assert registry.memo_get(
                memo_key(driver.kernel, V5E.name, D)) is None
        finally:
            set_decision_memo(prev)
        choose_or_default(driver.kernel, D, {"bm": -1})
        assert registry.memo_get(
            memo_key(driver.kernel, V5E.name, D)) is not None


# ---------------------------------------------------------------------------
# Engine integration: the step plan rides the serving loop
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestEngineStepPlan:
    def test_engine_builds_and_refreshes(self, clean):
        from repro.configs import get_config
        from repro.launch.serve import build_engine
        from repro.serving import Request

        cfg = get_config("llama3.2-1b", smoke=True)
        if not cfg.use_pallas:
            cfg = cfg.replace(use_pallas=True)
        engine = build_engine(cfg, batch=2, max_seq=16)
        plan = engine._step_plan
        assert plan is not None and len(plan) > 0
        assert not plan.stale()
        # a pinned override lands: next step rebuilds against it
        some_kernel, some_D = next(iter(plan.table))
        registry.note_override(some_kernel, V5E.name, dict(some_D),
                               dict(plan.table[(some_kernel, some_D)]))
        assert plan.stale()
        engine.submit(Request(rid=0, prompt=[3, 5], max_new_tokens=2))
        engine.run()
        assert engine._step_plan is not plan
        assert not engine._step_plan.stale()

    def test_engine_without_pallas_skips_plan(self, clean):
        from repro.configs import get_config
        from repro.launch.serve import build_engine

        cfg = get_config("llama3.2-1b", smoke=True)
        if cfg.use_pallas:
            cfg = cfg.replace(use_pallas=False)
        engine = build_engine(cfg, batch=1, max_seq=8)
        assert engine._step_plan is None
