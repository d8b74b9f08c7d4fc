"""Bucketed in-graph dispatch: the static shape lattice, the switch-index
decision, and the pow2 prefill chunking that rides on it.

Load-bearing properties:
  * Host (``bucket_of``) and graph (``bucket_keys``) rounding are
    bit-identical on every value, including the lattice edges and the
    out-of-range boundary -- this is what lets host replay stand in for
    the compiled graph in the bench gates and engine bucket stats.
  * ``BucketedDispatch.branch_index`` inside ``jax.jit`` agrees with
    ``host_index`` on hits, unplanned buckets, and out-of-range misses,
    and a miss lands on the trailing default branch (never a retrace).
  * The in-graph op path (``ops.matmul(..., in_graph=...)``) serves many
    raw shapes from ONE trace with outputs allclose to the unpadded
    reference.
  * ``ServingEngine._pow2_chunks`` covers any prompt length exactly with
    a log-bounded set of chunk sizes.
"""

import numpy as np
import pytest

from repro.core import (BucketLattice, V5E, matmul_spec, pad_to, pow2_span,
                        set_choice_listener)
from repro.core.device_plan import BucketedDispatch
from repro.core.plan import LaunchPlanTable


# ---------------------------------------------------------------------------
# Lattice primitives
# ---------------------------------------------------------------------------

class TestPrimitives:
    def test_pow2_span(self):
        assert pow2_span(64, 1024) == (64, 128, 256, 512, 1024)
        assert pow2_span(65, 1024) == (128, 256, 512, 1024)
        assert pow2_span(512, 512) == (512,)
        assert pow2_span(1, 1) == (1,)
        assert pow2_span(0, 4) == (1, 2, 4)

    def test_pad_to(self):
        import jax.numpy as jnp
        x = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
        out = pad_to(x, (4, 3))
        assert out.shape == (4, 3)
        np.testing.assert_array_equal(np.asarray(out)[2:], 0.0)
        np.testing.assert_array_equal(np.asarray(out)[:2], np.asarray(x))
        # None keeps a dimension; an exact match is the identity object
        assert pad_to(x, (None, 5)).shape == (2, 5)
        assert pad_to(x, (2, 3)) is x
        with pytest.raises(ValueError, match="smaller than extent"):
            pad_to(x, (1, 3))

    def test_from_axes_validates(self):
        lat = BucketLattice.from_axes("k", {"m": [256, 64, 64, 128]})
        assert lat.axes == (("m", (64, 128, 256)),)
        with pytest.raises(ValueError, match="positive"):
            BucketLattice.from_axes("k", {"m": [0, 64]})
        with pytest.raises(ValueError, match="positive"):
            BucketLattice.from_axes("k", {"m": []})


# ---------------------------------------------------------------------------
# Host/graph rounding bit-identity
# ---------------------------------------------------------------------------

class TestRounding:
    LAT = BucketLattice.from_axes("k", {"m": [8, 64, 256], "n": [128, 512]})

    def test_bucket_of_edges(self):
        lat = self.LAT
        assert lat.bucket_of({"m": 1, "n": 1}) == {"m": 8, "n": 128}
        assert lat.bucket_of({"m": 8, "n": 128}) == {"m": 8, "n": 128}
        assert lat.bucket_of({"m": 9, "n": 128}) == {"m": 64, "n": 128}
        assert lat.bucket_of({"m": 256, "n": 512}) == {"m": 256, "n": 512}
        # out of range: above the top, non-positive, missing param
        assert lat.bucket_of({"m": 257, "n": 128}) is None
        assert lat.bucket_of({"m": 0, "n": 128}) is None
        assert lat.bucket_of({"m": 8}) is None
        # extra keys ignored
        assert lat.bucket_of({"m": 8, "n": 128, "zz": 1}) is not None

    def test_host_graph_bit_identical_sweep(self):
        """Every (m, n) in a sweep spanning in-range, edges, and
        out-of-range: the jitted graph rounding must agree with the host
        exactly -- keys on hits, the in_range mask on misses."""
        import jax
        import jax.numpy as jnp

        lat = self.LAT

        @jax.jit
        def graph_round(raw):
            return lat.bucket_keys(raw)

        ms = [0, 1, 7, 8, 9, 63, 64, 65, 255, 256, 257, 1000]
        ns = [0, 1, 127, 128, 129, 511, 512, 513]
        for m in ms:
            for n in ns:
                keys, in_range = graph_round(
                    jnp.asarray([m, n], dtype=jnp.int32))
                host = lat.bucket_of({"m": m, "n": n})
                if host is None:
                    assert not bool(in_range), (m, n)
                else:
                    assert bool(in_range), (m, n)
                    assert tuple(int(v) for v in np.asarray(keys)) == \
                        (host["m"], host["n"]), (m, n)

    def test_padding_waste(self):
        lat = self.LAT
        assert lat.padding_waste({"m": 8, "n": 128}) == 0.0
        w = lat.padding_waste({"m": 32, "n": 128})
        assert w == pytest.approx(1.0 - 32 / 64)
        assert lat.padding_waste({"m": 999, "n": 128}) == 0.0  # miss

    def test_introspection(self):
        lat = self.LAT
        assert lat.data_params == ("m", "n")
        assert lat.n_buckets == 6
        assert lat.envelope() == {"m": [8, 64, 256], "n": [128, 512]}
        assert lat.envelope_shape() == {"m": 256, "n": 512}
        assert len(lat.all_buckets()) == 6
        assert {"m": 8, "n": 128} in lat.all_buckets()


# ---------------------------------------------------------------------------
# Feasibility-derived construction
# ---------------------------------------------------------------------------

class _StubSpec:
    """Spec stand-in with a controllable feasibility frontier."""
    name = "stub"
    data_params = ("m", "k")

    def candidates(self, D, hw):
        return [object()] if D["m"] <= 256 and D["k"] <= 512 else []


class TestFromSpec:
    def test_trims_infeasible_top(self):
        lat = BucketLattice.from_spec(_StubSpec(), {"m": (16, 1024),
                                                    "k": (64, 512)})
        assert dict(lat.axes)["m"] == (16, 32, 64, 128, 256)
        assert dict(lat.axes)["k"] == (64, 128, 256, 512)

    def test_fixed_params_skip_feasibility(self):
        lat = BucketLattice.from_spec(_StubSpec(), {"m": (16, 256)},
                                      fixed={"k": [7, 9999]})
        assert dict(lat.axes)["k"] == (7, 9999)

    def test_no_feasible_values_raises(self):
        with pytest.raises(ValueError, match="no feasible"):
            BucketLattice.from_spec(_StubSpec(), {"m": (512, 1024),
                                                  "k": (64, 64)})

    def test_real_spec_orders_by_data_params(self):
        spec = matmul_spec()
        lat = BucketLattice.from_spec(
            spec, {"k": (512, 512), "m": (64, 256), "n": (256, 256)})
        assert lat.data_params == tuple(
            d for d in spec.data_params if d in ("m", "n", "k"))


# ---------------------------------------------------------------------------
# BucketedDispatch: the switch-index decision
# ---------------------------------------------------------------------------

def _hand_dispatch():
    """Lattice + hand-built plan table; bucket (256, 512) left unplanned
    so the in-range-but-unplanned miss path is reachable."""
    lat = BucketLattice.from_axes("k", {"m": [64, 128, 256],
                                        "n": [256, 512]})
    shapes = {"m": np.array([64, 64, 128, 128, 256]),
              "n": np.array([256, 512, 256, 512, 256])}
    configs = {"bm": np.array([8, 8, 16, 16, 32]),
               "bn": np.array([128, 256, 128, 256, 128])}
    table = LaunchPlanTable.build("k", V5E.name, ("m", "n"), ("bm", "bn"),
                                  shapes, configs)
    return BucketedDispatch.build(lat, table, {"bm": 8, "bn": 128})


class TestBucketedDispatch:
    def test_static_config_set(self):
        disp = _hand_dispatch()
        # 5 planned rows, all distinct -> 5 configs + trailing default
        assert disp.configs == ((8, 128), (8, 256), (16, 128), (16, 256),
                                (32, 128))
        assert disp.n_branches == 6
        assert disp.config_dicts()[-1] == {"bm": 8, "bn": 128}
        assert len(disp.config_dicts()) == disp.n_branches

    def test_graph_matches_host_on_all_paths(self):
        import jax
        import jax.numpy as jnp

        disp = _hand_dispatch()

        @jax.jit
        def decide(dims):
            return disp.branch_index(dims)

        cases = [
            {"m": 64, "n": 256},    # exact bucket hit
            {"m": 33, "n": 200},    # rounded-up hit
            {"m": 129, "n": 300},   # rounds to (256, 512): unplanned miss
            {"m": 256, "n": 512},   # unplanned bucket, exact
            {"m": 300, "n": 256},   # out of range (above top)
            {"m": 0, "n": 256},     # out of range (non-positive)
        ]
        for D in cases:
            idx, hit = decide(jnp.asarray([D["m"], D["n"]], jnp.int32))
            h_idx, h_hit = disp.host_index(D)
            assert (int(idx), bool(hit)) == (h_idx, h_hit), D
        # hits resolve to a real branch, misses to the trailing default
        assert disp.host_index({"m": 64, "n": 256})[1] is True
        for D in cases[2:]:
            assert disp.host_index(D) == (len(disp.configs), False), D

    def test_host_config_matches_table(self):
        disp = _hand_dispatch()
        cfg, hit = disp.host_config({"m": 100, "n": 300})  # -> (128, 512)
        assert hit and cfg == {"bm": 16, "bn": 256}
        cfg, hit = disp.host_config({"m": 200, "n": 600})  # out of range
        assert not hit and cfg == {"bm": 8, "bn": 128}

    def test_observe_emits_bucket_events(self):
        disp = _hand_dispatch()
        events = []
        set_choice_listener(events.append)
        try:
            hit, waste = disp.observe({"m": 33, "n": 200}, n_coalesced=3)
            assert hit and waste == pytest.approx(
                disp.lattice.padding_waste({"m": 33, "n": 200}))
            miss_hit, miss_waste = disp.observe({"m": 999, "n": 256})
            assert not miss_hit and miss_waste == 0.0
        finally:
            set_choice_listener(None)
        assert [e.source for e in events] == ["bucket", "default"]
        assert events[0].n_coalesced == 3
        assert events[0].config == {"bm": 8, "bn": 128}
        assert events[1].config == {"bm": 8, "bn": 128}  # default branch

    def test_mismatched_params_rejected(self):
        lat = BucketLattice.from_axes("k", {"m": [64]})
        table = LaunchPlanTable.build(
            "k", V5E.name, ("m", "n"), ("bm",),
            {"m": np.array([64]), "n": np.array([256])},
            {"bm": np.array([8])})
        with pytest.raises(ValueError, match="do not match"):
            BucketedDispatch.build(lat, table, {"bm": 8})

    def test_empty_table_always_defaults(self):
        lat = BucketLattice.from_axes("k", {"m": [64, 128]})
        table = LaunchPlanTable.build(
            "k", V5E.name, ("m",), ("bm",),
            {"m": np.zeros(0, dtype=np.int64)},
            {"bm": np.zeros(0, dtype=np.int64)})
        disp = BucketedDispatch.build(lat, table, {"bm": 32})
        assert disp.n_branches == 1
        assert disp.host_index({"m": 64}) == (0, False)
        assert disp.host_config({"m": 64}) == ({"bm": 32}, False)


# ---------------------------------------------------------------------------
# The in-graph op path: one trace, many shapes
# ---------------------------------------------------------------------------

class TestInGraphOps:
    def _matmul_dispatch(self):
        lat = BucketLattice.from_axes(
            "k", {"m": [64, 128], "n": [256], "k": [256]})
        shapes = {"m": np.array([64, 128]), "n": np.array([256, 256]),
                  "k": np.array([256, 256])}
        configs = {"bm": np.array([8, 16]), "bn": np.array([128, 256]),
                   "bk": np.array([128, 128])}
        table = LaunchPlanTable.build(
            "k", V5E.name, ("m", "n", "k"), ("bm", "bn", "bk"),
            shapes, configs)
        return BucketedDispatch.build(lat, table,
                                      {"bm": 8, "bn": 128, "bk": 128})

    def test_matmul_one_trace_many_shapes(self):
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops

        disp = self._matmul_dispatch()
        traces = {"n": 0}

        @jax.jit
        def step(xp, yp, dims):
            traces["n"] += 1
            return ops.matmul(xp, yp, in_graph=disp, dims=dims)

        rng = np.random.default_rng(0)
        for (m, n, k) in [(40, 200, 200), (64, 256, 256), (100, 130, 250),
                          (128, 256, 256), (7, 9, 11)]:
            x = rng.standard_normal((m, k)).astype(np.float32)
            y = rng.standard_normal((k, n)).astype(np.float32)
            xp = pad_to(jnp.asarray(x), (128, 256))
            yp = pad_to(jnp.asarray(y), (256, 256))
            out = np.asarray(step(xp, yp,
                                  jnp.asarray([m, n, k], jnp.int32)))
            np.testing.assert_allclose(out[:m, :n], x @ y,
                                       rtol=1e-4, atol=1e-4)
        assert traces["n"] == 1

    def test_flash_in_graph_requires_causal(self):
        import jax.numpy as jnp

        from repro.kernels import ops

        disp = self._matmul_dispatch()   # any dispatch; check is upfront
        q = jnp.zeros((2, 8, 64), jnp.float32)
        with pytest.raises(ValueError, match="causal"):
            ops.flash_attention(q, q, q, causal=False, num_q_heads=2,
                                num_kv_heads=2, in_graph=disp)


# ---------------------------------------------------------------------------
# pow2 prefill chunking
# ---------------------------------------------------------------------------

class TestPow2Chunks:
    def test_exact_cover_and_bounds(self):
        from repro.serving.engine import ServingEngine

        for cmax in (1, 2, 8, 32, 64):
            allowed = {c for c in (1, 2, 4, 8, 16, 32, 64) if c <= cmax}
            for n in list(range(0, 70)) + [127, 128, 129, 1000]:
                chunks = ServingEngine._pow2_chunks(n, cmax)
                assert sum(chunks) == n, (n, cmax)
                assert all(c in allowed for c in chunks), (n, cmax)
                # descending, so at most one of each size below the cap:
                # the trace-cache bound log2(cmax)+1 plus repeats of cmax
                assert chunks == sorted(chunks, reverse=True), (n, cmax)
                below_cap = [c for c in chunks if c < cmax]
                assert len(below_cap) == len(set(below_cap)), (n, cmax)

    def test_trace_set_is_log_bounded(self):
        from repro.serving.engine import ServingEngine

        sizes = set()
        for n in range(1, 2000):
            sizes.update(ServingEngine._pow2_chunks(n, 64))
        assert sizes == {1, 2, 4, 8, 16, 32, 64}   # log2(64)+1 traces
