"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import V5E, registry
from repro.core.driver import set_choice_listener
from repro.kernels import ops, ref
from repro.kernels.flash_attention import (block_class,
                                           flash_attention_pallas,
                                           flash_block_counts)
from repro.kernels.matmul import matmul_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

RNG = np.random.RandomState(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-4


class TestMatmul:
    @pytest.mark.parametrize("m,n,k", [(64, 128, 128), (128, 256, 384),
                                       (256, 128, 512), (8, 128, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, m, n, k, dtype):
        x = (RNG.randn(m, k) * 0.5).astype(dtype)
        y = (RNG.randn(k, n) * 0.5).astype(dtype)
        out = matmul_pallas(x, y, bm=min(64, m), bn=128, bk=128,
                            interpret=True)
        exp = ref.matmul_ref(x, y)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   atol=_tol(dtype), rtol=_tol(dtype))

    @pytest.mark.parametrize("bm,bn,bk", [(8, 128, 128), (32, 256, 128),
                                          (64, 128, 256), (128, 128, 128)])
    def test_block_sweep(self, bm, bn, bk):
        m, n, k = 128, 256, 256
        x = RNG.randn(m, k).astype(np.float32)
        y = RNG.randn(k, n).astype(np.float32)
        out = matmul_pallas(x, y, bm=bm, bn=bn, bk=bk, interpret=True)
        np.testing.assert_allclose(out, x @ y, atol=1e-3, rtol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("variant", ["causal", "full", "window",
                                         "softcap", "gqa4"])
    def test_variants(self, variant):
        b, hq, hkv, s, d = 2, 4, 2, 256, 64
        if variant == "gqa4":
            hkv = 1
        kw = dict(causal=True)
        if variant == "full":
            kw = dict(causal=False)
        elif variant == "window":
            kw = dict(causal=True, window=64)
        elif variant == "softcap":
            kw = dict(causal=True, softcap=30.0)
        q = (RNG.randn(b * hq, s, d) * 0.3).astype(np.float32)
        k = (RNG.randn(b * hkv, s, d) * 0.3).astype(np.float32)
        v = (RNG.randn(b * hkv, s, d) * 0.3).astype(np.float32)
        out = flash_attention_pallas(q, k, v, num_q_heads=hq,
                                     num_kv_heads=hkv, bq=64, bkv=128,
                                     interpret=True, **kw)
        exp = ref.flash_attention_ref(q, k, v, num_q_heads=hq,
                                      num_kv_heads=hkv, **kw)
        np.testing.assert_allclose(out, exp, atol=2e-3, rtol=1e-3)

    @pytest.mark.parametrize("bq,bkv", [(64, 128), (128, 128), (256, 256)])
    def test_block_sweep(self, bq, bkv):
        b, hq, hkv, s, d = 1, 2, 2, 256, 64
        q = (RNG.randn(b * hq, s, d) * 0.3).astype(np.float32)
        k = (RNG.randn(b * hkv, s, d) * 0.3).astype(np.float32)
        v = (RNG.randn(b * hkv, s, d) * 0.3).astype(np.float32)
        out = flash_attention_pallas(q, k, v, num_q_heads=hq,
                                     num_kv_heads=hkv, bq=bq, bkv=bkv,
                                     interpret=True)
        exp = ref.flash_attention_ref(q, k, v, num_q_heads=hq,
                                      num_kv_heads=hkv)
        np.testing.assert_allclose(out, exp, atol=2e-3, rtol=1e-3)

    def test_chunked_ref_equals_naive(self):
        b, hq, hkv, s, d = 2, 4, 2, 256, 32
        q = (RNG.randn(b * hq, s, d) * 0.3).astype(np.float32)
        k = (RNG.randn(b * hkv, s, d) * 0.3).astype(np.float32)
        v = (RNG.randn(b * hkv, s, d) * 0.3).astype(np.float32)
        for kw in [dict(causal=True), dict(causal=False),
                   dict(causal=True, window=32),
                   dict(causal=True, softcap=20.0)]:
            a = ref.flash_attention_ref(q, k, v, num_q_heads=hq,
                                        num_kv_heads=hkv, **kw)
            c = ref.flash_attention_ref(q, k, v, num_q_heads=hq,
                                        num_kv_heads=hkv, q_chunk=64, **kw)
            np.testing.assert_allclose(a, c, atol=1e-5)


class TestMoeGmm:
    @pytest.mark.parametrize("e,g,k,n", [(2, 64, 128, 128), (4, 128, 256, 128),
                                         (8, 32, 128, 384)])
    def test_matches_ref(self, e, g, k, n):
        x = (RNG.randn(e, g, k) * 0.3).astype(np.float32)
        w = (RNG.randn(e, k, n) * 0.3).astype(np.float32)
        out = moe_gmm_pallas(x, w, bg=32, bn=128, bk=128, interpret=True)
        exp = ref.moe_gmm_ref(x, w)
        np.testing.assert_allclose(out, exp, atol=1e-3, rtol=1e-4)


class TestSSDScan:
    @pytest.mark.parametrize("chunk", [128, 256])
    @pytest.mark.parametrize("s", [256, 512])
    def test_matches_recurrence(self, chunk, s):
        bh, dh, n = 3, 64, 32
        x = (RNG.randn(bh, s, dh) * 0.5).astype(np.float32)
        dt = (0.01 + 0.5 * RNG.rand(bh, s)).astype(np.float32)
        B = (RNG.randn(bh, s, n) * 0.3).astype(np.float32)
        C = (RNG.randn(bh, s, n) * 0.3).astype(np.float32)
        A = (-0.5 - RNG.rand(bh)).astype(np.float32)
        out = ssd_scan_pallas(x, dt, B, C, A, chunk=chunk, interpret=True)
        exp = ref.ssd_scan_ref(x, dt, B, C, A)
        np.testing.assert_allclose(out, exp, atol=5e-3, rtol=1e-3)

    def test_parallel_form_matches_recurrence(self):
        from repro.models.layers import ssd_parallel
        bh, s, dh, n = 2, 512, 32, 16
        x = (RNG.randn(bh, s, dh) * 0.5).astype(np.float32)
        dt = (0.01 + 0.5 * RNG.rand(bh, s)).astype(np.float32)
        B = (RNG.randn(bh, s, n) * 0.3).astype(np.float32)
        C = (RNG.randn(bh, s, n) * 0.3).astype(np.float32)
        A = (-0.5 - RNG.rand(bh)).astype(np.float32)
        out = ssd_parallel(x, dt, B, C, A, chunk=128)
        exp = ref.ssd_scan_ref(x, dt, B, C, A)
        np.testing.assert_allclose(out, exp, atol=5e-3, rtol=1e-3)


def _score_mask(s, causal, window):
    qpos = np.arange(s)[:, None]
    kpos = np.arange(s)[None, :]
    mask = np.ones((s, s), dtype=bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    return mask


class TestFlashBlockSkip:
    @pytest.mark.parametrize("bq,bkv", [(64, 128), (128, 128), (256, 128),
                                        (128, 256)])
    @pytest.mark.parametrize("causal,window", [
        (True, None), (True, 128), (True, 129), (True, 191), (True, 200),
        (False, None), (False, 200)])
    def test_block_class_matches_mask(self, bq, bkv, causal, window):
        """Dead iff the block's mask has no live pair, interior iff it has
        no masked one, for every block of the square.  Windows 129 and 191
        put a block's nearest or farthest pair exactly at the window's
        edge."""
        s = 512
        mask = _score_mask(s, causal, window)
        for iq in range(s // bq):
            for ikv in range(s // bkv):
                block = mask[iq * bq:(iq + 1) * bq, ikv * bkv:(ikv + 1) * bkv]
                live, interior = block_class(iq, ikv, bq, bkv, causal,
                                             window)
                assert bool(live) == block.any(), (iq, ikv)
                assert bool(interior) == block.all(), (iq, ikv)

    @pytest.mark.parametrize("variant", ["causal", "window64", "window200",
                                         "softcap", "gqa4"])
    def test_skipping_matches_ref(self, variant):
        """At s 512 with 128 tiles 6 of each head's 16 blocks are dead."""
        rng = np.random.RandomState(16)
        b, hq, hkv, s, d = 1, 4, 2, 512, 64
        kw = dict(causal=True)
        if variant == "gqa4":
            hkv = 1
        elif variant.startswith("window"):
            kw["window"] = int(variant[len("window"):])
        elif variant == "softcap":
            kw["softcap"] = 30.0
        q = (rng.randn(b * hq, s, d) * 0.3).astype(np.float32)
        k = (rng.randn(b * hkv, s, d) * 0.3).astype(np.float32)
        v = (rng.randn(b * hkv, s, d) * 0.3).astype(np.float32)
        out = flash_attention_pallas(q, k, v, num_q_heads=hq,
                                     num_kv_heads=hkv, bq=128, bkv=128,
                                     interpret=True, **kw)
        exp = ref.flash_attention_ref(q, k, v, num_q_heads=hq,
                                      num_kv_heads=hkv, **kw)
        np.testing.assert_allclose(out, exp, atol=2e-3, rtol=1e-3)


class TestFlashBlockCounter:
    KERNEL = "flash_attn_d128_causal"
    TILES = {"bq": 2048, "bkv": 2048}

    @pytest.fixture()
    def clean(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KLARAPTOR_CACHE_DIR", str(tmp_path / "cache"))
        registry.clear()
        set_choice_listener(None)
        yield
        registry.clear()

    @pytest.mark.parametrize("bh,live,interior,total", [
        (64, 640, 384, 1024),       # internlm2-1.8b, 4 x 8192 tokens
        (128, 1280, 768, 2048),     # granite-4.0-h-small, 4 x 8192 tokens
        (1, 10, 6, 16)])
    def test_counts_at_the_forward_launch(self, bh, live, interior, total):
        assert flash_block_counts(bh, 8192, 8192, 2048, 2048, True,
                                  None) == {"live": live,
                                            "interior": interior,
                                            "total": total}

    def _trace_launch(self, **kw):
        from repro.trace import Tracer

        bf = jnp.bfloat16
        args = [jax.ShapeDtypeStruct((64, 8192, 128), bf),
                jax.ShapeDtypeStruct((32, 8192, 128), bf),
                jax.ShapeDtypeStruct((32, 8192, 128), bf)]
        fn = functools.partial(ops.flash_attention, num_q_heads=16,
                               num_kv_heads=8, use_pallas=True, **kw)
        with Tracer() as tracer:
            jax.eval_shape(fn, *args)
        return tracer.spans()

    def test_recorded_once_on_the_choose_path(self, clean):
        registry.note_override(self.KERNEL, V5E.name,
                               {"bh": 64, "sq": 8192, "skv": 8192},
                               self.TILES)
        spans = self._trace_launch()
        assert [s.attrs for s in spans] == [
            {"kernel": self.KERNEL, "live": 640, "interior": 384,
             "total": 1024}]

    def test_no_span_on_the_plan_hit_path(self, clean):
        class Plan:
            def resolve(self, kernel, D):
                return TestFlashBlockCounter.TILES

        assert self._trace_launch(plan=Plan()) == []
