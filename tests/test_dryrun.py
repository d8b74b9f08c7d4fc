"""Dry-run machinery tests (scaled-down mesh in a subprocess)."""

import json

import pytest

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable


class TestShapeApplicability:
    def test_long_500k_only_for_subquadratic(self):
        runnable = [a for a in ARCH_IDS
                    if shape_applicable(get_config(a), "long_500k")[0]]
        assert sorted(runnable) == ["granite-4.0-h-small",
                                    "jamba-1.5-large-398b", "mamba2-130m"]

    def test_cell_count(self):
        cells = 0
        for a in ARCH_IDS:
            for s in SHAPES:
                if shape_applicable(get_config(a), s)[0]:
                    cells += 1
        assert cells == 36   # 11 archs x 4 shapes - 8 long_500k skips

    def test_input_specs_cover_all_inputs(self):
        import jax
        from repro.launch.steps import input_specs
        for arch in ("llama3.2-1b", "internvl2-76b", "whisper-medium",
                     "mamba2-130m"):
            cfg = get_config(arch, smoke=True)
            for name, preset in SHAPES.items():
                if not shape_applicable(cfg, name)[0]:
                    continue
                specs = input_specs(cfg, preset)
                for leaf in jax.tree.leaves(specs):
                    assert isinstance(leaf, jax.ShapeDtypeStruct)
                if preset.kind == "decode":
                    assert "cache" in specs
                elif cfg.arch_kind == "vlm":
                    assert "patches" in specs
                elif cfg.arch_kind == "encdec":
                    assert "frames" in specs


@pytest.mark.slow
class TestDryRunCell(object):
    def test_lower_compile_and_analyze_small_mesh(self, devices8):
        out = devices8("""
            import os, json
            import jax
            from repro.analysis.hlo import collective_bytes
            from repro.configs import get_config
            from repro.configs.base import ShapePreset
            from repro.launch.mesh import make_mesh
            from repro.launch.steps import build_step

            cfg = get_config("llama3.2-1b", smoke=True)
            mesh = make_mesh((2, 4), ("data", "model"))
            preset = ShapePreset("t", "train", 128, 8)
            bundle = build_step(cfg, preset, mesh)
            with mesh:
                lowered = bundle.lower()
                compiled = lowered.compile()
            mem = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            st = collective_bytes(compiled.as_text())
            assert ca["flops"] > 0
            assert mem.temp_size_in_bytes > 0
            assert st.total_wire_bytes > 0   # sharded step must communicate
            print("ok", ca["flops"], st.total_wire_bytes)
        """, timeout=420)
        assert "ok" in out

    def test_multi_pod_axis_shards(self, devices8):
        out = devices8("""
            import jax
            from repro.configs import get_config
            from repro.configs.base import ShapePreset
            from repro.launch.mesh import make_mesh
            from repro.launch.steps import build_step

            cfg = get_config("llama3.2-1b", smoke=True)
            mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
            preset = ShapePreset("t", "train", 128, 8)
            bundle = build_step(cfg, preset, mesh)
            with mesh:
                compiled = bundle.lower().compile()
            # tokens (8, 129): batch must shard over pod*data = 4
            tok_sh = bundle.in_shardings[2]["tokens"]
            spec = tok_sh.spec
            assert spec[0] == ("pod", "data"), spec
            print("ok")
        """, timeout=420)
        assert "ok" in out

    def test_scan_correction_math_on_real_records(self):
        from repro.launch.dryrun import corrected_costs
        rec = {"full": {"flops": 50.0, "bytes": 10.0,
                        "collective_wire_bytes_per_device": 1.0},
               "diff": {"groups": 10,
                        "g1": {"flops": 15.0, "bytes": 2.0,
                               "collective_wire_bytes_per_device": 0.2},
                        "g2": {"flops": 20.0, "bytes": 3.0,
                               "collective_wire_bytes_per_device": 0.3}}}
        out = corrected_costs(rec)
        assert out["flops"] == pytest.approx(10 + 5 * 10)   # base + pg*G
        assert out["bytes"] == pytest.approx(1 + 1 * 10)
        # clamped from below by the full-depth compile's own measurement
        rec["full"]["flops"] = 100.0
        assert corrected_costs(rec)["flops"] == pytest.approx(100.0)
