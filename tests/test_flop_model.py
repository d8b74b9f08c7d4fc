"""FLOP densities that depend on the program parameters.

A KernelSpec's ``flops_per_point`` is a number for most kernels; the SSD
scan states its chunk-quadratic work as an expression over the chunk.  The
expression is evaluated row by row over a candidate table, diagnosed like a
constraint when it is wrong, and folded into the driver-cache key, while
every constant-density spec keeps the key it had.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from repro.core import (CandidateTable, SpecError, flash_attention_spec,
                        matmul_spec, moe_gmm_spec, polybench_suite,
                        ssd_scan_spec)
from repro.core.cache import _canonical, spec_fingerprint
from repro.core.kernel_spec import ssd_scan_flops

SSD_SHAPES = [
    {"bh": 384, "s": 8192, "chunkflops": 1},
    {"bh": 24, "s": 8192, "chunkflops": 1},
    {"bh": 48, "s": 65536, "chunkflops": 1},
    {"bh": 8, "s": 2048, "chunkflops": 1},
]


def _fingerprint_sha(spec) -> str:
    return hashlib.sha256(
        _canonical(spec_fingerprint(spec)).encode()).hexdigest()


class TestExpressionFlops:
    @pytest.mark.parametrize("D", SSD_SHAPES,
                             ids=lambda D: f"{D['bh']}x{D['s']}")
    def test_flops_total_agrees_with_traffic_table(self, D):
        spec = ssd_scan_spec()
        table = spec.candidates(D)
        assert len(table) > 1
        column = spec.traffic_table(D, table).flops_total
        for i, P in enumerate(table.rows()):
            assert column[i] == spec.flops_total(D, P), P

    def test_ssd_flops_rise_with_chunk(self):
        spec = ssd_scan_spec()
        D = SSD_SHAPES[0]
        table = spec.candidates(D)
        order = np.argsort(table["chunk"])
        flops = spec.traffic_table(D, table).flops_total[order]
        assert np.all(np.diff(flops) > 0), flops
        # Doubling the chunk nearly doubles the work once the (L, L) terms
        # dominate the chunk-independent state terms.
        assert flops[-1] / flops[-2] > 1.9

    def test_ssd_flops_count_the_body(self):
        """384 x 8192 tokens at chunk 2048: 1920 * 2048 + 4 * 128 * 64
        FLOPs each (the cumsum dot's 6 passes included)."""
        spec = ssd_scan_spec(d_head=64, d_state=128)
        D = SSD_SHAPES[0]
        want = 384 * 8192 * (1920 * 2048 + 4 * 128 * 64)
        assert spec.flops_total(D, {"chunk": 2048}) == want
        assert ssd_scan_flops(64, 128) == spec.flops_per_point

    def test_unknown_symbol_raises(self):
        spec = ssd_scan_spec()
        spec.flops_per_point = "chunk * d_model"
        D = SSD_SHAPES[0]
        with pytest.raises(SpecError, match="'d_model'"):
            spec.flops_total(D, {"chunk": 256})
        with pytest.raises(SpecError, match="flops_per_point"):
            spec.traffic_table(D, spec.candidates(D))

    def test_syntax_error_raises(self):
        spec = ssd_scan_spec()
        spec.flops_per_point = "chunk *"
        with pytest.raises(SpecError, match="not a valid Python expression"):
            spec.flops_total(SSD_SHAPES[0], {"chunk": 256})

    def test_scalar_fallback_per_row(self):
        """An expression NumPy cannot take whole is evaluated row by row;
        a row on which it fails is an error, not a silent value."""
        spec = ssd_scan_spec()
        D = SSD_SHAPES[0]
        table = CandidateTable.from_rows(("chunk",), [{"chunk": 128},
                                                     {"chunk": 1024}])
        spec.flops_per_point = "math.log2(chunk)"
        np.testing.assert_array_equal(
            spec.flops_per_point_batch(D, table), [7.0, 10.0])
        spec.flops_per_point = "math.log2(chunk - 128)"
        with pytest.raises(SpecError, match="fails at"):
            spec.flops_per_point_batch(D, table)

    def test_constant_density_unchanged(self):
        spec = matmul_spec()
        D = {"m": 1024, "n": 512, "k": 256}
        table = spec.candidates(D)
        flops = spec.traffic_table(D, table).flops_total
        assert flops.dtype == np.float64
        assert np.all(flops == 2.0 * 1024 * 512 * 256)
        assert spec.flops_total(D, table.row(0)) == 2.0 * 1024 * 512 * 256


# sha256 of the canonical spec fingerprint of every hand-written spec with a
# constant FLOP density, as they were before FLOP densities could depend on
# the program parameters.  A change here re-keys every cached driver.
CONSTANT_DENSITY_FINGERPRINTS = {
    "matmul_b16": "3da123d7b48296bce3f680a2db1ff524d1cdf67c324b5829e976d5c7106c5358",
    "matmul_b32": "51ff3dc7043931e7f1be6cb425a361efbb6f820e302af8f135f4b581e2d382cd",
    "moe_gmm_b16": "6f86f6afcf4e89870e70a538a1fc522da29e105759442dac5bfdb27666b6c702",
    "moe_gmm_b32": "147104325255a88f64e0c6636be51207247518b4117334c92b26117e833482fa",
    "flash_attn_d64_causal": "d7a83c2f5afa7131e907da95e9c2b9e58dda083e433058562c5a926414103cfa",
    "flash_attn_d64": "d7d5013df52f5fe949f31b228376ea554f1ef8eb4a9f54a97ec55731ddbddf59",
    "flash_attn_d128_causal": "06f0608e925940fc06272395e94d0acfcdebbf6a86e7354c1c02e00b8ea15de7",
    "flash_attn_d128": "41cf10bd225a8373030a7d88ee6145d73ec5b0369ea2298c01c1f52e5338f08b",
    "polybench.gemm": "a3a67d6169f284b0129f66ab1badf184f147c61bb7c5c8c54fae18d03dd685fd",
    "polybench.mm2_k1": "1be7a6a5b32c5a64ecb31de10cc0a0934cc07a5da0495ed25a7df7e643d14639",
    "polybench.mm2_k2": "85ad0284e260b008393b5e544fe57a1cc7053d320d76fbe379aea6c751a3809f",
    "polybench.mm3_k1": "643b0d6161d1a2f67940734cd8dd7db9d2e9c12496cc74dbd7badd02a493bdab",
    "polybench.mm3_k2": "7748f6d31228ec48cb290feb582e2d210519d6ea2bbba3fef51bec7ff2801207",
    "polybench.mm3_k3": "b135c3db9e80d41aeed4d6ff4c9b479cee9415a517d6675250e0f03b933de85c",
    "polybench.syrk": "a9f377f1eb9008047e4a004d52239de665792d63c05984c709efd0dcd26fdf2c",
    "polybench.syr2k": "fc7b54cf9d6577166013430b5a8d8a0acff51cfd141ecd11be3b3a62446db596",
    "polybench.corr": "2f2013c252cc78f721ceae249feb910502c97bc19a9bc5e781c54d25c4d6b6c1",
    "polybench.covar": "4c6bf546ae9ed79ce887e930bacc3d069b6332764740c5d35914342d7c0b4eec",
    "polybench.atax_k1": "9ede564e4b166721fc2729c9159aefb860d5a4c98689f3cb9ddac06dca429da1",
    "polybench.atax_k2": "a6e51b4159a957c1797c2a9ad0e82558c2e9d0c239aa046dee16557df58de118",
    "polybench.bicg_k1": "3894b1baa4f90beee7478a315f4805fbd48f72e977ae8cf2d4b64eb4aa9af4ec",
    "polybench.bicg_k2": "a5bc33e939021f499d37ee94f943d453ae8fb44e8a6b5410442185f46eea6b87",
    "polybench.mvt_k1": "53736a52a3e7ceee8a8f7ecd2d9084860048549f5f30dceb27efe4689c3a9425",
    "polybench.mvt_k2": "05e411bac711f58dd599f709a257ba58748e9e96b802d6eba456823c7362ef05",
    "polybench.gesummv": "8ce65e730c86cd596c05806c0174628cab50d607831afdeb228bfa28436fa4c5",
    "polybench.conv2d": "da29e8ac76810dad43011e61d202e11f37404968deaaa7c3e937ccff28259696",
    "polybench.conv3d": "a469b39a9515270e3e79842863ad135b895aa6ceef10fa26ccf15f4170b9b008",
    "polybench.fdtd_step1": "5777ab3ca90109fde3aed22d050de05dc2c02a6f46d387968939fa04b4cec2d0",
    "polybench.fdtd_step2": "a596ec74ab3f4c8a118a10f73626988fd68924439580f67fb3d03ff77efa30d1",
    "polybench.fdtd_step3": "304189c28f3c9c0e1cdcd262d90fdb1303d6244284b15aed5b0ebebc871cd4d0",
    "polybench.reduce": "0babb7e8794c2d128230bf0b505fab6b331d18f059d05e6c46c31acc5c073142",
    "polybench.mean": "f9dbddef2c81980cf455c2d41bb5bed31790378121875786c91ce161230be8c0",
    "polybench.std": "66e5f14341d355c6194116321f0e1811425b0ff3fdb6aa3cc443db271373552d",
    "polybench.gramschmidt_k1": "5c841a33fcf0733e1c56fd2aa27da426e9d8981a07a526adb94d2112e3409fb3",
    "polybench.gramschmidt_k2": "80af242d2933ecaaa119252eb0de6c05728c5a29c549a4a0cd1145b200f41984",
    "polybench.gramschmidt_k3": "2613d711d271c16aca8bbb4b807d468c06798589ae50e126c6d8ff019eed4e0a",
}
# The SSD spec's fingerprint while its density was frozen at chunk 256.
SSD_FROZEN_FINGERPRINT = \
    "664fe5c335d77110a5103739411c21d06b39612b1c85d0d33f7048597a63ccbf"


def _constant_density_specs() -> dict:
    specs = {}
    for db in (2, 4):
        for s in (matmul_spec(db), moe_gmm_spec(db)):
            specs[s.name] = s
    for head_dim in (64, 128):
        for causal in (True, False):
            s = flash_attention_spec(head_dim, causal)
            specs[s.name] = s
    for name, s in polybench_suite().items():
        specs[f"polybench.{name}"] = s
    return specs


class TestFingerprints:
    @pytest.mark.parametrize("name", sorted(CONSTANT_DENSITY_FINGERPRINTS))
    def test_constant_density_fingerprint_kept(self, name):
        spec = _constant_density_specs()[name]
        assert not isinstance(spec.flops_per_point, str)
        assert _fingerprint_sha(spec) == CONSTANT_DENSITY_FINGERPRINTS[name]

    def test_every_constant_density_spec_pinned(self):
        assert set(_constant_density_specs()) == \
            set(CONSTANT_DENSITY_FINGERPRINTS)

    def test_ssd_fingerprint_moved(self):
        """A driver built from the frozen-density SSD spec is never found
        under the corrected spec's cache key."""
        spec = ssd_scan_spec()
        assert spec_fingerprint(spec)["flops_per_point"] == ssd_scan_flops()
        assert _fingerprint_sha(spec) != SSD_FROZEN_FINGERPRINT
