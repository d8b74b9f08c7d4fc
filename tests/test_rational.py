"""Unit tests: rational programs, occupancy flowcharts, polynomials."""

import math

import numpy as np
import pytest

from repro.core import (Klaraptor, Polynomial, RationalFunction,
                        V5eSimulator, cuda_occupancy_program, matmul_spec,
                        tpu_pipeline_occupancy_program)
from repro.core.rational_program import (Ceil, Const, Floor, Max, Min, Select,
                                         ceil_div, const, floor_div, var)


class TestPolynomial:
    def test_eval(self):
        # 2 + 3*x*y + x^2 over vars (x, y)
        p = Polynomial(("x", "y"), ((0, 0), (1, 1), (2, 0)),
                       np.array([2.0, 3.0, 1.0]))
        X = np.array([[1.0, 2.0], [3.0, 0.5]])
        np.testing.assert_allclose(p(X), [2 + 6 + 1, 2 + 4.5 + 9])

    def test_source_roundtrip(self):
        p = Polynomial(("x", "y"), ((0, 0), (1, 2)), np.array([1.5, -2.0]))
        src = p.to_source()
        for x, y in [(1.0, 2.0), (3.0, -1.0)]:
            assert eval(src) == pytest.approx(p(np.array([[x, y]]))[0])


class TestRationalFunction:
    def test_eval_and_json(self):
        rf = RationalFunction.from_coeffs(
            ("x",), [(0,), (1,)], np.array([1.0, 2.0]),
            [(0,), (1,)], np.array([1.0, 0.5]))
        X = np.array([[2.0]])
        assert rf(X)[0] == pytest.approx((1 + 4) / (1 + 1))
        rf2 = RationalFunction.from_json(rf.to_json())
        assert rf2(X)[0] == pytest.approx(rf(X)[0])

    def test_denominator_stability(self):
        rf = RationalFunction.from_coeffs(
            ("x",), [(0,)], np.array([1.0]),
            [(0,), (1,)], np.array([-1.0, 1.0]))   # pole at x=1
        X = np.linspace(0.5, 2.0, 10)[:, None]
        assert not rf.denominator_sign_stable(X)
        X2 = np.linspace(2.0, 5.0, 10)[:, None]
        assert rf.denominator_sign_stable(X2)


class TestExprIR:
    def test_arith_and_pieces(self):
        x, y = var("x"), var("y")
        e = Select(x > y, x * const(2.0), y - x)
        assert e.count_pieces() == 2
        assert e.eval({"x": 3.0, "y": 1.0}) == 6.0
        assert e.eval({"x": 1.0, "y": 5.0}) == 4.0

    def test_floor_ceil_div(self):
        assert floor_div(var("a"), var("b")).eval({"a": 7, "b": 2}) == 3
        assert ceil_div(var("a"), var("b")).eval({"a": 7, "b": 2}) == 4

    def test_vectorized_eval(self):
        e = Min(var("a"), const(4.0)) + Max(var("b"), const(0.0))
        out = e.eval({"a": np.array([1.0, 9.0]), "b": np.array([-1.0, 2.0])})
        np.testing.assert_allclose(out, [1.0, 6.0])

    def test_source_matches_eval(self):
        e = Select(var("x") >= const(2.0),
                   Floor(var("x") / const(2.0)) * const(3.0),
                   Ceil(var("x") * const(0.5)))
        src = e.to_source()
        for xv in (0.5, 1.9, 2.0, 7.3):
            got = eval(src, {"math": math, "x": xv})
            assert got == pytest.approx(float(e.eval({"x": xv})))


class TestOccupancyPrograms:
    def test_cuda_occupancy_five_pieces(self):
        # Fig. 2 has exactly 5 terminating leaves.
        occ = cuda_occupancy_program()
        assert occ.outputs["B_active"].count_pieces() == 5

    def test_cuda_occupancy_vs_bruteforce(self):
        occ = cuda_occupancy_program()
        H = dict(R_max=65536, Z_max=49152, T_max=1024, B_max=32, W_max=64)

        def brute(R, Z, T):
            if T > H["T_max"] or R * T > H["R_max"]:
                return 0
            if Z > 0 and Z > H["Z_max"]:
                return 0
            b = min(H["B_max"], H["T_max"] // T, H["R_max"] // (R * T))
            if Z > 0:
                b = min(b, H["Z_max"] // Z)
            return min((b * T) // 32, H["W_max"])

        rng = np.random.RandomState(0)
        for _ in range(200):
            R = int(rng.choice([16, 32, 64, 128, 255]))
            Z = int(rng.choice([0, 1024, 4096, 65536]))
            T = int(rng.choice([32, 128, 256, 512, 1024, 2048]))
            got = occ.eval({**H, "R": R, "Z": Z, "T": T}, output="W_active")
            assert got == brute(R, Z, T), (R, Z, T)

    def test_tpu_occupancy(self):
        occ = tpu_pipeline_occupancy_program()
        env = {"vmem": 128 * 2 ** 20, "stage_bytes": 30 * 2 ** 20}
        assert occ.eval(env, output="buffers") == 3
        assert occ.eval(env, output="overlap") == 1.0
        env["stage_bytes"] = 100 * 2 ** 20
        assert occ.eval(env, output="buffers") == 1
        assert occ.eval(env, output="overlap") == 0.0

    def test_flowchart_export(self):
        occ = cuda_occupancy_program()
        chart = occ.to_flowchart()
        assert "decide" in chart and "compute" in chart


# ---------------------------------------------------------------------------
# D-specialization of rational programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def builds():
    """A matmul driver build (registry untouched) whose fits the
    specialization tests bind."""
    sim = V5eSimulator(noise=0.03, seed=7)
    kl = Klaraptor(sim, cache=False)
    return {"matmul": kl.build_driver(matmul_spec(), repeats=2,
                                      max_configs_per_size=16,
                                      register=False)}


class TestSpecialize:
    def test_expr_folding_matches_eval(self):
        from repro.core import ceil_div, var
        e = ceil_div(var("m"), var("bm")) * ceil_div(var("n"), var("bn"))
        s = e.specialize({"m": 4096, "n": 2048})
        assert s.free_vars() == {"bm", "bn"}
        env = {"bm": np.array([8.0, 128.0]), "bn": np.array([128.0, 256.0])}
        np.testing.assert_array_equal(
            s.eval(env), e.eval({**env, "m": 4096, "n": 2048}))

    def test_select_folds_and_pieces_shrink(self):
        from repro.core import RationalProgram, Select, const, var
        e = Select(var("d") >= const(128), var("p") * 2.0, var("p") * 3.0)
        prog = RationalProgram("t", ("d", "p"), {"E": e})
        assert prog.count_pieces() == 2
        spec = prog.specialize({"d": 256})
        assert spec.count_pieces() == 1           # decision node folded away
        assert spec.inputs == ("p",)
        assert float(spec.eval({"p": 5.0})) == 10.0

    def test_full_binding_gives_constant(self):
        from repro.core import Const, var
        e = (var("a") + var("b")) / var("c")
        s = e.specialize({"a": 6, "b": 2, "c": 4})
        assert isinstance(s, Const) and s.value == 2.0

    def test_partially_bound_fitted_leaf(self, builds):
        """Specializing a program whose Fitted leaves mix D and P must pin
        the D inputs (partial application) so the specialized program is
        evaluable with only its advertised inputs."""
        from repro.core import (Fitted, RationalProgram, build_time_program,
                                matmul_spec, var)
        fits = {m: f.function for m, f in builds["matmul"].fits.items()}
        prog = build_time_program(matmul_spec(), fits)
        D = {"m": 4096.0, "n": 2048.0, "k": 1024.0}
        sp = prog.specialize(D)
        assert not (set(sp.inputs) & set(D))
        P = {"bm": np.array([128.0, 256.0]), "bn": np.array([512.0, 512.0]),
             "bk": np.array([512.0, 1024.0])}
        np.testing.assert_allclose(sp.eval(P), prog.eval({**D, **P}))
        # a partially-applied leaf refuses source emission (codegen never
        # produces one; silently wrong source would be worse)
        leaf = Fitted("g", fits["mem_step"], {"bm": 8.0})
        with pytest.raises(NotImplementedError):
            leaf.to_source()
