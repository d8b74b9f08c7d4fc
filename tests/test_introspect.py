"""repro.introspect: automatic KernelSpec extraction from Pallas kernels.

Covers the tentpole acceptance bar: introspected specs behaviorally
identical to all four hand-written tier-1 specs (grid, candidates, traffic,
stage bytes, feasible set, chosen config at 8 representative shapes), plus
the two auto-specced kernels running the full pipeline with zero
hand-written spec code, the kernel-content cache-key invalidation, and the
hardened constraint-string evaluation (SpecError satellite).
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import (Klaraptor, SpecError, V5eSimulator, cache_key,
                        choose_or_default, dtype_bytes, matmul_spec,
                        registry, selection_ratio)
from repro.introspect import (GridSpec, IntrospectError, auto_register,
                              capture_kernel, spec_from_kernel, trace_points)
from repro.introspect.tier1 import tier1_pairs

# 8 representative shapes per tier-1 kernel (all extents sublane-aligned,
# the lattice real serving traffic lives on).
EQUIV_SHAPES = {
    "matmul_b16": [
        {"m": 512, "n": 512, "k": 512},
        {"m": 1024, "n": 1024, "k": 1024},
        {"m": 2048, "n": 1024, "k": 4096},
        {"m": 128, "n": 8192, "k": 1024},
        {"m": 4096, "n": 4096, "k": 2048},
        {"m": 8192, "n": 256, "k": 512},
        {"m": 2048, "n": 2048, "k": 2048},
        {"m": 256, "n": 1024, "k": 8192},
    ],
    "flash_attn_d128_causal": [
        {"bh": 8, "sq": 1024, "skv": 1024},
        {"bh": 16, "sq": 2048, "skv": 2048},
        {"bh": 32, "sq": 4096, "skv": 4096},
        {"bh": 16, "sq": 2048, "skv": 8192},
        {"bh": 64, "sq": 512, "skv": 512},
        {"bh": 8, "sq": 8192, "skv": 8192},
        {"bh": 48, "sq": 1024, "skv": 4096},
        {"bh": 24, "sq": 4096, "skv": 1024},
    ],
    "moe_gmm_b16": [
        {"e": 8, "g": 1024, "k": 2048, "n": 1024},
        {"e": 4, "g": 4096, "k": 1024, "n": 2048},
        {"e": 16, "g": 512, "k": 1024, "n": 1024},
        {"e": 2, "g": 2048, "k": 4096, "n": 512},
        {"e": 8, "g": 256, "k": 512, "n": 2048},
        {"e": 32, "g": 1024, "k": 1024, "n": 1536},
        {"e": 4, "g": 8192, "k": 2048, "n": 1024},
        {"e": 8, "g": 2048, "k": 2048, "n": 2048},
    ],
    "ssd_scan_h64_n128": [
        {"bh": 8, "s": 2048, "chunkflops": 1},
        {"bh": 16, "s": 8192, "chunkflops": 1},
        {"bh": 64, "s": 65536, "chunkflops": 1},
        {"bh": 32, "s": 4096, "chunkflops": 1},
        {"bh": 8, "s": 32768, "chunkflops": 1},
        {"bh": 128, "s": 1024, "chunkflops": 1},
        {"bh": 48, "s": 16384, "chunkflops": 1},
        {"bh": 16, "s": 131072, "chunkflops": 1},
    ],
}


@pytest.fixture(scope="module")
def pairs():
    """(hand spec, introspected spec) for every tier-1 kernel."""
    out = {}
    for fn, gs, hand in tier1_pairs():
        out[hand.name] = (hand, spec_from_kernel(fn, gs))
    return out


@pytest.fixture(scope="module")
def built_pairs(pairs):
    """Drivers built from hand and introspected specs with identical probe
    settings and noise streams (no cache -- the specs fingerprint apart)."""
    out = {}
    for name, (hand, intro) in pairs.items():
        b_h = Klaraptor(V5eSimulator(noise=0.03, seed=3),
                        cache=False).build_driver(
            hand, repeats=2, max_configs_per_size=12, register=False)
        b_i = Klaraptor(V5eSimulator(noise=0.03, seed=3),
                        cache=False).build_driver(
            intro, repeats=2, max_configs_per_size=12, register=False)
        out[name] = (b_h.driver, b_i.driver)
    return out


class TestTier1Equivalence:
    @pytest.mark.parametrize("name", sorted(EQUIV_SHAPES))
    def test_structural_equivalence(self, pairs, name):
        """Same candidates, grid steps, stage bytes, and oracle times at
        every representative shape."""
        hand, intro = pairs[name]
        assert intro.data_params == hand.data_params
        assert intro.program_params == hand.program_params
        sim = V5eSimulator(noise=0.0, seed=0)
        for D in EQUIV_SHAPES[name]:
            th, ti = hand.candidates(D), intro.candidates(D)
            assert th.params == ti.params
            assert len(th) == len(ti) > 0
            for p in th.params:
                assert np.array_equal(th[p], ti[p]), (D, p)
            assert np.array_equal(hand.grid_steps_batch(D, th),
                                  intro.grid_steps_batch(D, ti))
            assert np.array_equal(hand.vmem_stage_bytes_batch(D, th),
                                  intro.vmem_stage_bytes_batch(D, ti))
            # The opaque oracle cannot tell the two specs apart: identical
            # per-config times means identical traffic, padding and FLOPs.
            t_h = sim.true_time_batch(hand.traffic_table(D, th))
            t_i = sim.true_time_batch(intro.traffic_table(D, ti))
            assert np.array_equal(t_h, t_i), D

    @pytest.mark.parametrize("name", sorted(EQUIV_SHAPES))
    def test_chosen_configs_identical(self, built_pairs, name):
        """Drivers built from the two specs choose the same config at every
        representative shape."""
        drv_h, drv_i = built_pairs[name]
        for D in EQUIV_SHAPES[name]:
            assert drv_h.choose(D) == drv_i.choose(D), D

    def test_feasibility_agrees_scalar(self, pairs):
        hand, intro = pairs["matmul_b16"]
        D = {"m": 1024, "n": 1024, "k": 1024}
        for P in ({"bm": 128, "bn": 512, "bk": 512},
                  {"bm": 8, "bn": 128, "bk": 128},
                  {"bm": 100, "bn": 512, "bk": 512}):   # misaligned bm
            assert hand.feasible(D, P) == intro.feasible(D, P), P


class TestDerivation:
    def test_flash_kv_residency(self, pairs):
        """The k/v index map's GQA arithmetic depends on the batch axis and
        the kv axis, never the query axis -- found by jaxpr data flow."""
        _, intro = pairs["flash_attn_d128_causal"]
        names = [a.name for a in intro.grid]
        k_op = intro.operands[1]
        dep_pos = sorted(names.index(d) for d in k_op.deps)
        assert dep_pos == [0, 2]

    def test_ssd_decay_fetched_per_batch_row(self, pairs):
        """The A (decay) plane's index map ignores the chunk axis: one
        fetch per batch row (block residency across the scan)."""
        _, intro = pairs["ssd_scan_h64_n128"]
        decay = intro.operands[4]
        assert decay.deps == (intro.grid[0].name,)
        assert decay.tile == (1, 128)

    def test_flops_and_alignment_derived(self, pairs):
        hand, intro = pairs["matmul_b16"]
        # flops/mxu were NOT hinted for matmul -- the cost walk found them.
        assert intro.flops_per_point == hand.flops_per_point == 2.0
        assert intro.mxu_fraction == 1.0
        assert "bm % 8 == 0" in intro.constraints
        assert "bn % 128 == 0" in intro.constraints

    def test_flash_lane_alignment_from_intermediate(self, pairs):
        """bkv is never the minor axis of any *operand* tile; only the
        (bq, bkv) score matrix inside the body makes it lane-critical."""
        _, intro = pairs["flash_attn_d128_causal"]
        assert "bkv % 128 == 0" in intro.constraints
        assert "bq % 8 == 0" in intro.constraints

    def test_trace_points_unambiguous(self):
        from repro.introspect.tier1 import moe_gmm_grid_spec
        (D1, P1), (D2, P2) = trace_points(moe_gmm_grid_spec())
        vals1 = list(D1.values()) + list(P1.values())
        assert len(set(vals1)) == len(vals1)
        assert all(P1[p] != P2[p] for p in P1)
        assert all(D1[d] != D2[d] for d in D1)

    def test_flops_expression_hint_passes_through(self, pairs):
        """ssd's P-dependent FLOP hint reaches the derived spec as the
        expression the hand spec carries, not a frozen number."""
        from repro.core.kernel_spec import ssd_scan_flops

        hand, intro = pairs["ssd_scan_h64_n128"]
        assert intro.flops_per_point == hand.flops_per_point \
            == ssd_scan_flops(64, 128)

    @pytest.mark.parametrize("name,flops", [
        ("matmul_b16", 2.0), ("flash_attn_d128_causal", 256.0),
        ("moe_gmm_b16", 2.0)])
    def test_derived_constant_densities_stay_numbers(self, pairs, name,
                                                     flops):
        _, intro = pairs[name]
        assert isinstance(intro.flops_per_point, float)
        assert intro.flops_per_point == flops

    def test_p_dependent_flops_need_hint(self):
        """ssd's chunk-quadratic FLOP density is rejected without a hint."""
        from repro.introspect.tier1 import ssd_scan_grid_spec
        from repro.kernels.ssd_scan import ssd_scan_pallas

        gs = ssd_scan_grid_spec()
        gs.flops_per_point = None
        with pytest.raises(IntrospectError, match="flops_per_point"):
            spec_from_kernel(ssd_scan_pallas, gs)


class TestSourceFingerprint:
    def test_stable_across_traces(self):
        from repro.kernels.reduce import colsum_grid_spec, colsum_pallas
        s1 = spec_from_kernel(colsum_pallas, colsum_grid_spec())
        s2 = spec_from_kernel(colsum_pallas, colsum_grid_spec())
        assert s1.source_fingerprint == s2.source_fingerprint

    def test_changed_kernel_body_changes_cache_key(self):
        """Editing the kernel body (here: eps) must route to fresh tuning
        artifacts: different source fingerprint -> different cache key."""
        from repro.core import V5E
        from repro.kernels.layernorm import (layernorm_grid_spec,
                                             layernorm_pallas)

        s1 = spec_from_kernel(layernorm_pallas, layernorm_grid_spec(512))
        s2 = spec_from_kernel(layernorm_pallas,
                              layernorm_grid_spec(512, eps=1e-3))
        assert s1.source_fingerprint != s2.source_fingerprint
        hyper = {"repeats": 2}
        assert cache_key(s1, V5E, hyper) != cache_key(s2, V5E, hyper)

    def test_hand_spec_fingerprint_unset(self):
        assert matmul_spec().source_fingerprint == ""


class TestAutoKernelPipeline:
    def test_end_to_end_zero_hand_spec(self, tmp_path, monkeypatch):
        """introspect -> collect/fit -> choose -> dispatch -> telemetry,
        for both auto kernels, no hand-written spec anywhere."""
        monkeypatch.setenv("KLARAPTOR_CACHE_DIR", str(tmp_path))
        registry.clear()
        from repro.launch.serve import build_auto_kernels
        from repro.telemetry import Telemetry

        sim = V5eSimulator(noise=0.03, seed=5)
        kernels = build_auto_kernels(d_model=512, tune_device=sim)
        assert [ak.name for ak in kernels] == \
            ["layernorm_c512_b16", "colsum_b16"]
        tel = Telemetry([ak.spec for ak in kernels], sim, seed=0)
        tel.install()
        try:
            for ak in kernels:
                D = ({"r": 4096} if "layernorm" in ak.name
                     else {"r": 4096, "c": 2048})
                from repro.core.driver import get_driver
                drv = get_driver(ak.name)
                assert drv is not None
                r = selection_ratio(ak.spec, sim, drv, D)
                assert r["ratio"] >= 0.7, r
                cfg = choose_or_default(ak.name, D, ak.defaults)
                assert cfg == drv.choose(D)
            import json
            j = json.loads(tel.exporter.json())
            assert j["counters"]["choices_by_source"].get("driver", 0) >= 2
        finally:
            tel.uninstall()
            registry.clear()

    def test_fit_config_uses_derived_alignment(self):
        from repro.kernels.reduce import colsum_grid_spec, colsum_pallas
        ak = auto_register(colsum_pallas, colsum_grid_spec())
        assert ak.alignments() == {"br": 8, "bc": 128}
        fitted = ak.fit_config({"br": 512, "bc": 1024}, {"r": 384, "c": 640})
        assert 384 % fitted["br"] == 0 and fitted["br"] % 8 == 0
        assert 640 % fitted["bc"] == 0 and fitted["bc"] % 128 == 0

    def test_auto_register_idempotent(self):
        from repro.introspect import auto_kernels, get_auto
        from repro.kernels.reduce import colsum_grid_spec, colsum_pallas
        a1 = auto_register(colsum_pallas, colsum_grid_spec())
        a2 = auto_register(colsum_pallas, colsum_grid_spec())
        assert a1 is a2
        assert get_auto(a1.name) is a1
        assert a1.name in auto_kernels()

    def test_ops_dispatch_interpret_correct(self):
        """The auto-specced ops produce correct numerics through the full
        dispatch path (default config, no tuning) in interpret mode."""
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops, ref

        x = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.float32)
        res = jax.random.normal(jax.random.PRNGKey(1), (64, 256), jnp.float32)
        g = jax.random.normal(jax.random.PRNGKey(2), (256,), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(3), (256,), jnp.float32)
        y = ops.layernorm(x, res, g, b, use_pallas=True)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.layernorm_ref(x, res, g, b)),
            atol=1e-5)
        s = ops.blocked_colsum(x, use_pallas=True)
        np.testing.assert_allclose(
            np.asarray(s), np.asarray(ref.colsum_ref(x)), rtol=1e-5)


class TestSpecErrorHardening:
    def test_unknown_symbol_named(self):
        spec = matmul_spec()
        spec.constraints = spec.constraints + ("bm <= vmen",)   # typo
        with pytest.raises(SpecError, match="vmen"):
            spec.candidates({"m": 512, "n": 512, "k": 512})

    def test_syntax_error_diagnosed(self):
        spec = matmul_spec()
        spec.constraints = ("bm <=",)
        with pytest.raises(SpecError, match="not a valid Python expression"):
            spec.candidates({"m": 512, "n": 512, "k": 512})

    def test_no_builtins_in_namespace(self):
        spec = matmul_spec()
        spec.constraints = ("len([bm]) == 1",)
        with pytest.raises(SpecError, match="'len'"):
            spec.candidates({"m": 512, "n": 512, "k": 512})

    def test_math_and_np_still_allowed(self):
        spec = matmul_spec()
        spec.constraints = spec.constraints + (
            "bm <= math.inf", "np.maximum(bm, 8) >= 8")
        table = spec.candidates({"m": 512, "n": 512, "k": 512})
        assert len(table) > 0


class TestDtypeTableDedup:
    def test_single_canonical_table(self):
        from repro.analysis import hlo
        from repro.core import device_model
        assert hlo.DTYPE_BYTES is device_model.DTYPE_BYTES

    def test_dtype_bytes_lookups(self):
        import jax.numpy as jnp
        assert dtype_bytes("bf16") == 2
        assert dtype_bytes(jnp.bfloat16) == 2
        assert dtype_bytes(np.float32) == 4
        assert dtype_bytes(np.dtype("int8")) == 1

    def test_introspected_dtypes_from_table(self, pairs):
        _, intro = pairs["ssd_scan_h64_n128"]
        assert [op.dtype_bytes for op in intro.operands] == \
            [2, 4, 2, 2, 4, 2, 4, 4]


class TestIntrospectErrors:
    def test_not_a_pallas_kernel(self):
        import jax.numpy as jnp

        gs = GridSpec(
            name="plain_fn", data_params=("n",), program_params=("b",),
            make_args=lambda D: (
                __import__("jax").ShapeDtypeStruct((D["n"],), jnp.float32),))
        with pytest.raises(IntrospectError, match="pallas_call"):
            spec_from_kernel(lambda x, b: x * 2, gs)

    def test_capture_reports_scratch(self):
        from repro.introspect.tier1 import flash_attention_grid_spec
        from repro.kernels.flash_attention import flash_attention_pallas

        gs = flash_attention_grid_spec()
        (D1, P1), _ = trace_points(gs)
        cap = capture_kernel(flash_attention_pallas, gs, D1, P1)
        assert sum(op.is_scratch for op in cap.operands) == 3
        assert sum(op.is_output for op in cap.operands) == 1
