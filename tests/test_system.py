"""End-to-end system tests: training convergence, fault-tolerant resume,
serving engine, and the full KLARAPTOR tune->train integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapePreset
from repro.core import Klaraptor, V5eSimulator, matmul_spec
from repro.core.driver import registry
from repro.launch.train import TrainLoop
from repro.serving import Request
from repro.launch.serve import build_engine


def _loop(tmp_path=None, steps_cfg=None, arch="llama3.2-1b", **kw):
    cfg = get_config(arch, smoke=True)
    if steps_cfg:
        cfg = cfg.replace(**steps_cfg)
    preset = ShapePreset("t", "train", 64, 4)
    return TrainLoop(cfg, preset, mesh=None,
                     ckpt_dir=str(tmp_path) if tmp_path else None, **kw)


@pytest.mark.slow
class TestTraining:
    def test_loss_decreases(self):
        loop = _loop()
        loop.init_state()
        hist = loop.run(50, log_every=5)
        first, last = hist[0]["loss"], hist[-1]["loss"]
        assert last < first - 0.4, (first, last)
        assert np.isfinite(last)

    def test_checkpoint_resume_bit_exact(self, tmp_path):
        # run A: 30 steps straight through
        a = _loop(tmp_path / "a", ckpt_every=10)
        a.init_state()
        a.run(30, log_every=30)
        a.save(block=True)

        # run B: crash at step 21 (after the step-20 checkpoint), restore
        b = _loop(tmp_path / "b", ckpt_every=10)
        b.init_state()
        with pytest.raises(RuntimeError):
            b.run(30, fail_at=21)
        b.manager.wait()   # let the in-flight async step-20 save land
        b2 = _loop(tmp_path / "b", ckpt_every=10)
        resumed_from = b2.restore_or_init()
        assert resumed_from == 20
        b2.run(30, log_every=30)
        b2.save(block=True)

        pa = jax.tree.leaves(a.params)
        pb = jax.tree.leaves(b2.params)
        for x, y in zip(pa, pb):
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))

    def test_moe_arch_trains(self):
        loop = _loop(arch="qwen3-moe-235b-a22b")
        loop.init_state()
        hist = loop.run(20, log_every=5)
        assert hist[-1]["loss"] < hist[0]["loss"]
        assert np.isfinite(hist[-1]["router_aux"])

    def test_hybrid_arch_trains(self):
        loop = _loop(arch="jamba-1.5-large-398b")
        loop.init_state()
        hist = loop.run(12, log_every=4)
        assert hist[-1]["loss"] < hist[0]["loss"] + 0.1


@pytest.mark.slow
class TestServing:
    def test_engine_completes_requests(self):
        cfg = get_config("llama3.2-1b", smoke=True)
        engine = build_engine(cfg, batch=2, max_seq=32)
        for i in range(5):
            engine.submit(Request(rid=i, prompt=[3 + i, 7, 11],
                                  max_new_tokens=4))
        finished = engine.run()
        assert len(finished) == 5
        for r in finished:
            assert 1 <= len(r.output) <= 4
            assert all(0 <= t < cfg.padded_vocab for t in r.output)

    def test_continuous_batching_reuses_slots(self):
        cfg = get_config("llama3.2-1b", smoke=True)
        engine = build_engine(cfg, batch=2, max_seq=32)
        for i in range(6):
            engine.submit(Request(rid=i, prompt=[2, 3],
                                  max_new_tokens=2 + i % 3))
        finished = engine.run()
        assert len(finished) == 6   # 6 requests through 2 slots

    def test_greedy_is_deterministic(self):
        cfg = get_config("llama3.2-1b", smoke=True)
        outs = []
        for _ in range(2):
            engine = build_engine(cfg, batch=1, max_seq=16, seed=3)
            engine.submit(Request(rid=0, prompt=[5, 9], max_new_tokens=5))
            outs.append(engine.run()[0].output)
        assert outs[0] == outs[1]

    def test_async_front_end_matches_sync(self):
        """The async scheduler (chunked pow2 prefill) must produce greedy
        outputs identical to the token-by-token sync loop, from one
        decode-step trace across mixed prompt lengths."""
        cfg = get_config("llama3.2-1b", smoke=True)
        outs = {}
        for mode in ("sync", "async"):
            engine = build_engine(cfg, batch=2, max_seq=48, seed=0,
                                  prefill_chunk=8)
            for i in range(6):
                prompt = [2 + (5 * i + j) % 20 for j in range(3 + 4 * i)]
                engine.submit(Request(rid=i, prompt=prompt,
                                      max_new_tokens=3))
            finished = (engine.run() if mode == "sync"
                        else engine.run_async())
            outs[mode] = {r.rid: list(r.output) for r in finished}
            if mode == "async":
                assert engine.compile_counts["decode_step"] == 1
                # pow2 chunking: at most log2(prefill_chunk)+1 traces
                assert 1 <= engine.compile_counts["prefill_chunk"] <= 4
        assert outs["sync"] == outs["async"]

    def test_async_submit_while_running(self):
        """Requests submitted after start() are picked up by the scheduler
        thread; drain() returns them all."""
        cfg = get_config("llama3.2-1b", smoke=True)
        engine = build_engine(cfg, batch=2, max_seq=32)
        engine.start()
        try:
            for i in range(4):
                engine.submit(Request(rid=i, prompt=[3 + i, 7, 11],
                                      max_new_tokens=2))
            finished = engine.drain(timeout=120)
        finally:
            engine.stop()
        assert sorted(r.rid for r in finished) == [0, 1, 2, 3]
        assert all(1 <= len(r.output) <= 2 for r in finished)

    def test_mamba_engine(self):
        cfg = get_config("mamba2-130m", smoke=True)
        engine = build_engine(cfg, batch=2, max_seq=16)
        engine.submit(Request(rid=0, prompt=[4, 8, 15], max_new_tokens=3))
        finished = engine.run()
        assert len(finished) == 1 and len(finished[0].output) >= 1


class TestKlaraptorIntegration:
    def test_tuned_kernels_in_model_forward(self):
        """Build a driver, register it, and run a Pallas-enabled forward:
        ops.matmul must consult the driver (paper step 6)."""
        registry.clear()
        sim = V5eSimulator(noise=0.03, seed=2)
        kl = Klaraptor(sim)
        build = kl.build_driver(matmul_spec(dtype_bytes=4), repeats=2,
                                max_configs_per_size=12)
        # spec name is matmul_b32 (f32); ops.matmul consults it for f32 inputs
        from repro.kernels import ops
        x = jnp.asarray(np.random.RandomState(0).randn(256, 256), jnp.float32)
        w = jnp.asarray(np.random.RandomState(1).randn(256, 256), jnp.float32)
        out = ops.matmul(x, w, use_pallas=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   atol=1e-3)
        hist = build.driver.namespace["_HISTORY"]
        assert ((256, 256, 256) in hist), hist  # decision was consulted
        registry.clear()
