"""Pallas TPU kernels (+ jnp oracles) for the substrate's compute hot-spots.

The paper's contribution (heSRPT) is kernel-free scheduler math; these kernels
serve the *scheduled substrate*: flash attention (causal/SWA/GQA), the Mamba2
SSD chunked scan, and the RG-LRU linear recurrence.
"""

from repro.kernels import alloc, ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan

__all__ = [
    "alloc",
    "flash_attention",
    "ops",
    "ref",
    "rglru_scan",
    "ssd_scan",
]
