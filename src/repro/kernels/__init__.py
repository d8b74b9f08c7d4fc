"""Pallas TPU kernels (tunable hot spots) + pure-jnp oracles.

Each kernel module exposes ``<name>_pallas`` (pl.pallas_call + BlockSpec
VMEM tiling); ``ops`` wraps them with KLARAPTOR driver dispatch; ``ref``
holds the oracles used both for testing and for the CPU dry-run path.
"""

from . import ops, ref
from .flash_attention import flash_attention_pallas
from .layernorm import layernorm_grid_spec, layernorm_pallas
from .matmul import matmul_pallas
from .moe_experts import moe_experts_pallas
from .moe_gmm import moe_gmm_pallas
from .reduce import colsum_grid_spec, colsum_pallas
from .ssd_scan import ssd_scan_pallas

__all__ = [
    "ops", "ref", "flash_attention_pallas", "matmul_pallas",
    "moe_gmm_pallas", "moe_experts_pallas", "ssd_scan_pallas",
    "layernorm_pallas", "layernorm_grid_spec",
    "colsum_pallas", "colsum_grid_spec",
]
