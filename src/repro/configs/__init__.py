"""Architecture registry: ``--arch <id>`` resolution.

Each module exposes full() and smoke() ModelConfigs; ``polybench`` is the
paper's own kernel-suite workload (not an LM).  A module may also expose
``chip_share()``, one chip's share of a stated deployment (its experts,
its pipeline stage), under its own id in ``SHARES``.
"""

from repro.configs import (gemma2_2b, granite4_h_small, grok1_314b,
                           internlm2_1_8b, internvl2_76b, jamba1_5_large,
                           llama3_2_1b, mamba2_130m, polybench, qwen3_14b,
                           qwen3_moe_235b, whisper_medium)
from repro.configs.base import SHAPES, ShapePreset, shape_applicable

_MODULES = (
    gemma2_2b, internlm2_1_8b, llama3_2_1b, qwen3_14b, jamba1_5_large,
    internvl2_76b, mamba2_130m, whisper_medium, qwen3_moe_235b, grok1_314b,
    granite4_h_small,
)

REGISTRY = {m.ARCH_ID: m for m in _MODULES}
ARCH_IDS = tuple(REGISTRY)
SHARES = {granite4_h_small.SHARE_ID: granite4_h_small}

# Architectures whose optimizer state is stored bf16 (DESIGN.md section 4).
BF16_OPT_STATE = {"jamba-1.5-large-398b", "qwen3-moe-235b-a22b",
                  "grok-1-314b"}


def get_config(arch: str, smoke: bool = False):
    if arch in SHARES:
        mod = SHARES[arch]
        return mod.smoke() if smoke else mod.chip_share()
    if arch not in REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: "
                       f"{sorted(REGISTRY) + sorted(SHARES)}")
    mod = REGISTRY[arch]
    return mod.smoke() if smoke else mod.full()


__all__ = ["REGISTRY", "ARCH_IDS", "SHARES", "SHAPES", "ShapePreset",
           "shape_applicable", "get_config", "BF16_OPT_STATE", "polybench"]
