"""Distribution tests: sharding rules, collectives, PP, elastic restore.

Multi-device cases run in subprocesses with fake CPU devices so this
process keeps its single-device view (see conftest).
"""

import numpy as np
import pytest

from repro.distributed.sharding import Sharder, decode_rules, train_rules
from repro.distributed.fault_tolerance import (StragglerMonitor, Watchdog,
                                               retry_loop,
                                               FaultToleranceError)
from repro.distributed.pipeline_parallel import bubble_fraction


class TestSharderRules:
    def test_pspec_divisibility_fallback(self):
        # no mesh: everything replicated
        sh = Sharder(mesh=None)
        assert sh.dp_size() == 1

    def test_train_rules_have_core_axes(self):
        r = train_rules()
        assert r["batch"] == ("pod", "data")
        assert r["heads"] == "model"
        assert r["vocab"] == "model"
        assert r["act_seq"] == "model"       # sequence parallelism default

    def test_decode_rules_modes(self):
        assert decode_rules("heads")["cache_heads"] == "model"
        assert decode_rules("seq")["cache_seq"] == "model"
        long = decode_rules("long")
        assert long["cache_seq"] == ("data", "model")
        assert long["batch"] is None


@pytest.mark.slow
class TestMeshSharding:
    def test_pspec_on_real_mesh(self, devices8):
        devices8("""
            import jax
            from jax.sharding import PartitionSpec as P
            from repro.distributed.sharding import Sharder, train_rules
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            sh = Sharder(mesh=mesh, rules=train_rules(fsdp=True))
            # divisible dims shard; indivisible fall back to replication
            ps = sh.pspec((8, 512), ("embed", "heads"))
            assert ps == P("data", "model"), ps
            ps2 = sh.pspec((7, 512), ("embed", "heads"))
            assert ps2 == P(None, "model"), ps2
            assert ("embed", "data", 7) in sh.dropped
            # same mesh axis never used twice
            ps3 = sh.pspec((8, 8), ("experts", "mlp"))
            assert ps3 == P("model", None), ps3
            print("ok")
        """)

    def test_train_step_executes_on_mesh(self, devices8):
        devices8("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.configs.base import ShapePreset
            from repro.launch.mesh import make_mesh
            from repro.launch.steps import build_step
            from repro.models import init_params
            from repro.optim import adamw_init

            cfg = get_config("llama3.2-1b", smoke=True)
            mesh = make_mesh((2, 4), ("data", "model"))
            preset = ShapePreset("t", "train", 64, 4)
            bundle = build_step(cfg, preset, mesh)
            with mesh:
                params = init_params(bundle.model.specs(),
                                     jax.random.PRNGKey(0))
                from repro.launch.steps import _opt_cfg_for
                opt = adamw_init(_opt_cfg_for(cfg), params)
                toks = jnp.asarray(np.random.randint(
                    0, cfg.vocab_size, (4, 65)), jnp.int32)
                step = jax.jit(bundle.fn,
                               in_shardings=bundle.in_shardings,
                               out_shardings=bundle.out_shardings)
                p2, o2, m = step(params, opt, {"tokens": toks})
                loss1 = float(m["loss"])
                p3, o3, m2 = step(p2, o2, {"tokens": toks})
                loss2 = float(m2["loss"])
            assert np.isfinite(loss1) and np.isfinite(loss2)
            assert loss2 < loss1   # two steps on same batch must descend
            print("ok", loss1, loss2)
        """, timeout=420)

    def test_hierarchical_and_compressed_pmean(self, devices8):
        devices8("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.distributed.collectives import (hierarchical_pmean,
                                                       compressed_pmean)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("pod", "data"))
            x = jnp.arange(8.0).reshape(8, 1) * jnp.ones((8, 16))

            def f(x):
                return hierarchical_pmean({"g": x}, "data", "pod")["g"]
            out = jax.jit(jax.shard_map(f, mesh=mesh,
                          in_specs=P(("pod","data")), out_specs=P(),
                          check_vma=False))(x)
            np.testing.assert_allclose(np.asarray(out), 3.5)

            def g(x):
                m, r = compressed_pmean({"g": x}, "data", "pod")
                return m["g"]
            out2 = jax.jit(jax.shard_map(g, mesh=mesh,
                           in_specs=P(("pod","data")), out_specs=P(),
                           check_vma=False))(x)
            # int8 quantization: within one quant step of the true mean
            assert abs(float(out2[0,0]) - 3.5) < 0.1, float(out2[0,0])
            print("ok")
        """)

    def test_gpipe_matches_sequential(self, devices8):
        devices8("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.distributed.pipeline_parallel import gpipe_forward
            n_stages, n_micro, mb, dim = 4, 8, 2, 16
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("pipe",))
            rng = np.random.RandomState(0)
            ws = jnp.asarray(rng.randn(n_stages, dim, dim) * 0.3,
                             jnp.float32)
            x = jnp.asarray(rng.randn(n_micro, mb, dim), jnp.float32)

            def stage_fn(w, h):
                return jnp.tanh(h @ w)

            out = gpipe_forward(stage_fn, ws, x, mesh, axis="pipe")
            # sequential reference
            ref = x
            for s in range(n_stages):
                ref = jnp.tanh(ref @ ws[s])
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)
            print("ok")
        """)

    def test_elastic_restore_across_meshes(self, devices8):
        devices8("""
            import jax, jax.numpy as jnp, numpy as np, tempfile
            from repro.checkpoint import CheckpointManager
            from repro.distributed.elastic import (elastic_restore,
                                                   shardings_for_specs)
            from repro.distributed.sharding import Sharder, train_rules
            from repro.models.module import ParamSpec, init_params

            specs = {"w": ParamSpec((8, 16), jnp.float32,
                                    ("embed", "heads"))}
            d = tempfile.mkdtemp()
            mgr = CheckpointManager(d, async_save=False)

            from repro.launch.mesh import make_mesh
            mesh1 = make_mesh((2, 4), ("data", "model"))
            sh1 = Sharder(mesh=mesh1, rules=train_rules())
            params = init_params(specs, jax.random.PRNGKey(0))
            params = jax.device_put(params, shardings_for_specs(specs, sh1))
            mgr.save(1, params)

            # restore onto a DIFFERENT mesh shape (4x2)
            mesh2 = make_mesh((4, 2), ("data", "model"))
            sh2 = Sharder(mesh=mesh2, rules=train_rules())
            restored, _, step = elastic_restore(
                mgr, specs, sh2,
                {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)})
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(params["w"]))
            assert restored["w"].sharding.mesh.shape["data"] == 4
            print("ok")
        """)


class TestFaultTolerance:
    def test_watchdog_fires(self):
        w = Watchdog(timeout_s=0.2).start()
        import time
        time.sleep(0.5)
        assert w.fired
        w.stop()

    def test_watchdog_beats_keep_alive(self):
        import time
        w = Watchdog(timeout_s=0.4).start()
        for _ in range(4):
            time.sleep(0.1)
            w.beat()
        assert not w.fired
        w.stop()

    def test_watchdog_fires_once_until_reset(self):
        import time
        fires = []
        w = Watchdog(timeout_s=0.15, on_timeout=lambda: fires.append(1))
        w.start()
        time.sleep(0.5)
        # fired exactly once despite several timeout windows elapsing
        assert w.fired and len(fires) == 1
        w.stop()

    def test_watchdog_rearms_after_reset(self):
        import time
        fires = []
        w = Watchdog(timeout_s=0.15, on_timeout=lambda: fires.append(1))
        w.start()
        time.sleep(0.4)
        assert w.fired and len(fires) == 1
        w.reset()               # re-arm: a revived worker is watchable again
        assert not w.fired
        time.sleep(0.1)
        assert not w.fired      # reset also refreshed the heartbeat
        time.sleep(0.4)
        assert w.fired and len(fires) == 2
        w.stop()

    def test_straggler_monitor(self):
        mon = StragglerMonitor(n_hosts=4, threshold=2.0, patience=2)
        assert mon.observe([1.0, 1.0, 1.0, 1.0]) == []
        assert mon.observe([1.0, 1.0, 1.0, 5.0]) == []
        assert mon.observe([1.0, 1.0, 1.0, 5.0]) == [3]

    def test_retry_loop_survives_failures(self):
        calls = {"n": 0}

        def run(start):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("boom")

        failures = retry_loop(run, restore_fn=lambda: 0, max_failures=5)
        assert failures == 2

    def test_retry_loop_gives_up(self):
        def run(start):
            raise RuntimeError("always")

        with pytest.raises(FaultToleranceError):
            retry_loop(run, restore_fn=lambda: 0, max_failures=2)

    def test_bubble_fraction(self):
        assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
